"""Build + invoke the native C++ baseline pipeline (native/baseline_pipeline.cc).

Used by tests: cross-validation of the independent C++ reimplementation
against the Python pipelines."""

from __future__ import annotations

import json
import os
import subprocess
import tempfile

import numpy as np

from celestia_app_tpu.utils import native_build

BINARY = os.path.join(native_build.NATIVE_DIR, "baseline_pipeline")


def build(timeout: int = 180) -> bool:
    """(Re)build via make — the Makefile's dependency tracking means a stale
    binary is rebuilt whenever the source changed. False if no toolchain."""
    try:
        native_build.make("baseline_pipeline", timeout=timeout)
        return os.path.exists(BINARY)
    except Exception as e:
        # no toolchain in this container: the caller falls back to the
        # Python baseline; say so once at debug level instead of nothing
        from celestia_app_tpu import obs

        _log = obs.get_logger("utils.native")
        _log.debug("native baseline build unavailable", err=e)
        return False


def run(ods: np.ndarray, reps: int = 3, timeout: int = 600) -> dict:
    """Run the pipeline on a (k, k, 512) ODS: {"cpu_ms": ..., "data_root": hex}."""
    k = ods.shape[0]
    assert ods.shape == (k, k, 512) and ods.dtype == np.uint8
    if not build():
        raise RuntimeError("native baseline toolchain unavailable")
    with tempfile.NamedTemporaryFile(delete=False, suffix=".ods") as f:
        f.write(ods.tobytes())
        path = f.name
    try:
        out = subprocess.run(
            [BINARY, path, str(k), str(reps)],
            check=True, capture_output=True, text=True, timeout=timeout,
        )
    finally:
        os.unlink(path)
    return json.loads(out.stdout)
