"""Blobstream verify CLI (x/blobstream client verify analog).

Builds a real home past one data-commitment window, then proves a share
through the full chain: share proof -> block data root -> the covering
attestation's data-commitment tuple root (the value an EVM Blobstream
contract stores per nonce — ref client/verify.go:27-38).
"""

import json
import os
import subprocess
import sys

import pytest

ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _run(*argv, timeout=420):
    return subprocess.run(
        [sys.executable, "-m", "celestia_app_tpu", *argv],
        capture_output=True, text=True, timeout=timeout, env=ENV,
    )


@pytest.mark.slow
def test_verify_cli_proves_share_to_attestation(tmp_path):
    home = str(tmp_path / "home")
    assert _run("init", "--home", home, "--chain-id", "verify-cli-1",
                "--engine", "host").returncode == 0
    # one full default data-commitment window (400) + 1
    assert _run("start", "--home", home, "--blocks", "401",
                "--block-time", "0").returncode == 0

    out = _run("verify", "--home", home, "--height", "123",
               "--start", "0", "--end", "1")
    assert out.returncode == 0, out.stderr[-800:]
    doc = json.loads(out.stdout)
    assert doc["verified"] is True
    assert doc["attestation_range"][0] <= 123 < doc["attestation_range"][1]
    assert len(doc["data_commitment_root"]) == 64

    # a height past the attested window is refused with a clear error
    out2 = _run("verify", "--home", home, "--height", "401")
    assert out2.returncode == 1
    assert "not covered" in out2.stderr
