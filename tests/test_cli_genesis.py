"""The genesis toolkit (genutil analog): add-account / gentx /
collect-gentxs / validate, and the pinned-hash download-genesis verifier.

Reference: cmd/celestia-appd/cmd/root.go:126-133 registers genutil's
InitCmd/CollectGenTxsCmd/AddGenesisAccountCmd/GenTxCmd/ValidateGenesisCmd;
cmd/download_genesis.go pins known networks' genesis SHA-256.
"""

import hashlib
import json
import os

import pytest

from celestia_app_tpu import cli
from celestia_app_tpu.chain.crypto import PrivateKey


def _genesis(home):
    with open(os.path.join(home, "genesis.json")) as f:
        return json.load(f)


def _init(home, capsys=None):
    assert cli.main(["init", "--home", home, "--chain-id", "gen-test"]) == 0


def test_gentx_ceremony_produces_a_working_chain(tmp_path, capsys):
    """init -> add-account -> gentx -> collect-gentxs -> validate -> the
    merged genesis actually boots an App and the new validator proposes."""
    home = str(tmp_path / "home")
    _init(home)
    new = PrivateKey.from_seed(b"new-val")
    addr = new.public_key().address().hex()

    assert cli.main(["genesis", "add-account", "--home", home,
                     "--address", addr, "--balance", "1000000"]) == 0
    assert cli.main(["genesis", "gentx", "--home", home, "--seed", "new-val",
                     "--moniker", "newcomer", "--power", "7"]) == 0
    assert cli.main(["genesis", "collect-gentxs", "--home", home]) == 0
    assert cli.main(["genesis", "validate", "--home", home]) == 0

    genesis = _genesis(home)
    merged = {v["operator"]: v for v in genesis["validators"]}
    assert addr in merged and merged[addr]["power"] == 7
    assert merged[addr]["pubkey"] == new.public_key().compressed.hex()

    # the merged genesis boots and the validator set includes the newcomer
    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.state import InfiniteGasMeter

    app = App(chain_id="gen-test")
    app.init_chain(genesis)
    vals = app.staking.validators(app._deliver_ctx(InfiniteGasMeter()))
    assert any(op.hex() == addr and power == 7 for op, power in vals)


def test_add_account_rejects_duplicates_and_bad_hex(tmp_path):
    home = str(tmp_path / "home")
    _init(home)
    first = _genesis(home)["accounts"][0]["address"]
    assert cli.main(["genesis", "add-account", "--home", home,
                     "--address", first, "--balance", "1"]) == 1
    assert cli.main(["genesis", "add-account", "--home", home,
                     "--address", "zz" * 20, "--balance", "1"]) == 1
    assert cli.main(["genesis", "add-account", "--home", home,
                     "--address", "ab" * 4, "--balance", "1"]) == 1
    assert cli.main(["genesis", "add-account", "--home", home,
                     "--address", "cd" * 20, "--balance", "-3"]) == 1


def test_collect_rejects_forged_and_unfunded_gentxs(tmp_path):
    home = str(tmp_path / "home")
    _init(home)
    gdir = os.path.join(home, "gentx")

    # unfunded operator: signature fine, but no genesis account
    assert cli.main(["genesis", "gentx", "--home", home, "--seed", "ghost",
                     "--power", "3"]) == 0
    assert cli.main(["genesis", "collect-gentxs", "--home", home]) == 1

    # forged power: flip a field after signing -> signature must fail
    addr = PrivateKey.from_seed(b"ghost").public_key().address().hex()
    assert cli.main(["genesis", "add-account", "--home", home,
                     "--address", addr, "--balance", "10"]) == 0
    path = [os.path.join(gdir, p) for p in os.listdir(gdir)][0]
    with open(path) as f:
        doc = json.load(f)
    doc["power"] = 9999
    with open(path, "w") as f:
        json.dump(doc, f)
    assert cli.main(["genesis", "collect-gentxs", "--home", home]) == 1


def test_validate_catches_structural_rot(tmp_path):
    home = str(tmp_path / "home")
    _init(home)
    genesis = _genesis(home)
    genesis["validators"][0]["power"] = 0
    genesis["accounts"].append({"address": "ab" * 20, "balance": -5})
    with open(os.path.join(home, "genesis.json"), "w") as f:
        json.dump(genesis, f)
    assert cli.main(["genesis", "validate", "--home", home]) == 1


def test_download_genesis_verifies_local_pin(tmp_path):
    """Zero-egress path: a local file matching the pin verifies; a
    tampered one is rejected; unknown chain-ids are refused."""
    home = str(tmp_path / "net")
    os.makedirs(home)
    body = b'{"fake": "genesis"}'
    with open(os.path.join(home, "genesis.json"), "wb") as f:
        f.write(body)
    # not the pinned hash -> mismatch
    assert cli.main(["download-genesis", "celestia", "--home", home]) == 1
    # pin the hash of our file via monkeypatching the table copy
    cli._GENESIS_SHA256["unit-test-net"] = hashlib.sha256(body).hexdigest()
    try:
        assert cli.main(["download-genesis", "unit-test-net",
                         "--home", home]) == 0
    finally:
        del cli._GENESIS_SHA256["unit-test-net"]
    assert cli.main(["download-genesis", "no-such-net", "--home", home]) == 1


def test_config_get_set_roundtrip(tmp_path):
    """config.Cmd analog: get whole config, set a known key (JSON-typed),
    refuse unknown keys."""
    home = str(tmp_path / "home")
    _init(home)
    assert cli.main(["config", "get", "--home", home]) == 0
    assert cli.main(["config", "set", "min_gas_price", "0.004",
                     "--home", home]) == 0
    with open(os.path.join(home, "config.json")) as f:
        assert json.load(f)["min_gas_price"] == 0.004
    assert cli.main(["config", "get", "min_gas_price", "--home", home]) == 0
    assert cli.main(["config", "set", "no_such_key", "1", "--home", home]) == 1
    assert cli.main(["config", "get", "no_such_key", "--home", home]) == 1


def test_pay_for_blob_input_file_multi_blob(tmp_path):
    """The reference's --input-file JSON schema submits several blobs in
    ONE PFB (x/blob/client/cli/payforblob.go:60-76)."""
    home = str(tmp_path / "home")
    _init(home)
    path = os.path.join(home, "blobs.json")
    with open(path, "w") as f:
        json.dump({"Blobs": [
            {"namespaceID": "0x" + "01" * 10, "blob": "0x48656c6c6f"},
            {"namespaceID": "0x" + "02" * 10, "blob": "0xdeadbeef"},
        ]}, f)
    assert cli.main(["tx", "pay-for-blob", "--home", home,
                     "--from-seed", "0", "--input-file", path]) == 0
    # empty Blobs array is a usage error, not a crash
    with open(path, "w") as f:
        json.dump({"Blobs": []}, f)
    assert cli.main(["tx", "pay-for-blob", "--home", home,
                     "--from-seed", "0", "--input-file", path]) == 2


def test_store_trace_records_commits(tmp_path):
    """`start --trace` appends {op, key, len, height} JSON lines for every
    committed store write (SetCommitMultiStoreTracer analog,
    ref app/app.go:194 + cmd/root.go:243)."""
    home = str(tmp_path / "home")
    _init(home)
    assert cli.main(["start", "--home", home, "--blocks", "2",
                     "--block-time", "0.05", "--listen", "0",
                     "--trace"]) == 0
    path = os.path.join(home, "data", "store_trace.jsonl")
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert lines, "no trace lines written"
    assert {ln["op"] for ln in lines} <= {"write", "delete"}
    assert all(set(ln) == {"op", "key", "len", "height"} for ln in lines)
    # every line carries the height of the block whose flush wrote it:
    # exactly blocks 1 and 2 (no off-by-one attribution to N-1)
    heights = {ln["height"] for ln in lines}
    assert heights == {1, 2}, heights


@pytest.mark.parametrize("doc,home_cfg,want,warned", [
    ({"produce_batch": 4}, {}, {}, True),
    ({"produce_batch": 1, "poll": 0.05}, {"snapshot_interval_blocks": 7},
     {"poll": 0.05, "snapshot_interval": 7}, True),
    ({"poll": 0.05}, {"snapshot_keep_recent": 3},
     {"poll": 0.05, "snapshot_keep": 3}, False),
])
def test_an_older_reactor_json_still_starts(tmp_path, capsys, doc, home_cfg,
                                            want, warned):
    """A reactor.json written by an older version may name an option
    since removed: the validator's reactor config drops it with one
    warning line, keeps every other key, and fills the snapshot knobs
    from the home config."""
    home = str(tmp_path)
    with open(os.path.join(home, "reactor.json"), "w") as f:
        json.dump(doc, f)
    cfg = cli._reactor_config(home, home_cfg)
    for key, value in want.items():
        assert getattr(cfg, key) == value
    err = capsys.readouterr().err
    assert err.count("ignoring") == int(warned)
