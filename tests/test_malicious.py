"""Adversarial fixtures: honest validators must reject malicious proposals.

Reference analogs: test/util/malicious/{tree,out_of_order_builder,
out_of_order_prepare}.go and app/test/consistent_apphash_test.go (the
regression pin lives in test_apphash_pin.py)."""

import numpy as np
import pytest

from celestia_app_tpu.chain.block import Block, Header
from celestia_app_tpu.chain.node import Node
from celestia_app_tpu.client.tx_client import TxClient
from celestia_app_tpu.da.blob import Blob
from celestia_app_tpu.da.namespace import Namespace
from celestia_app_tpu.testing import malicious
from celestia_app_tpu.utils import nmt_host

from test_app import make_app


def _pfb_txs(signer, privs, rng, n=3):
    """Raw blob txs for n blobs with distinct namespaces."""
    addr = privs[0].public_key().address()
    txs = []
    for i in range(n):
        blob = Blob(
            Namespace.v0(bytes([i + 1]) * 7),
            rng.integers(0, 256, 600 + i * 480, dtype=np.uint8).tobytes(),
        )
        raw = signer.create_pay_for_blobs(addr, [blob], fee=200_000, gas_limit=1_000_000)
        signer.accounts[addr].sequence += 1
        txs.append(raw)
    return txs


def test_honest_tree_rejects_out_of_order_push():
    tree = nmt_host.NmtTree()
    tree.push(b"\x02" * 29, b"data")
    with pytest.raises(ValueError):
        tree.push(b"\x01" * 29, b"data")
    blind = malicious.BlindNmtTree()
    blind.push(b"\x02" * 29, b"x")
    blind.push(b"\x01" * 29, b"y")  # no error: the malicious hasher
    assert blind.root() is not None


def test_out_of_order_proposal_rejected():
    rng = np.random.default_rng(0)
    app, signer, privs = make_app()
    txs = _pfb_txs(signer, privs, rng)

    honest = app.prepare_proposal(txs, t=1_700_000_100.0)
    assert app.process_proposal(honest.block) is True

    forged = malicious.out_of_order_prepare(app, txs, t=1_700_000_100.0)
    # the forged root differs and carries a swapped square
    assert forged.header.data_hash != honest.block.header.data_hash
    assert app.process_proposal(forged) is False


def test_forged_data_root_rejected():
    rng = np.random.default_rng(1)
    app, signer, privs = make_app()
    txs = _pfb_txs(signer, privs, rng, n=2)
    honest = app.prepare_proposal(txs, t=1_700_000_100.0).block
    h = honest.header
    bad_root = bytes([h.data_hash[0] ^ 1]) + h.data_hash[1:]
    forged = Block(
        header=Header(
            chain_id=h.chain_id, height=h.height, time_unix=h.time_unix,
            data_hash=bad_root, square_size=h.square_size, app_hash=h.app_hash,
            proposer=h.proposer, app_version=h.app_version,
            last_block_hash=h.last_block_hash,
        ),
        txs=honest.txs,
    )
    assert app.process_proposal(forged) is False


def test_wrong_square_size_rejected():
    rng = np.random.default_rng(2)
    app, signer, privs = make_app()
    txs = _pfb_txs(signer, privs, rng, n=2)
    honest = app.prepare_proposal(txs, t=1_700_000_100.0).block
    h = honest.header
    forged = Block(
        header=Header(
            chain_id=h.chain_id, height=h.height, time_unix=h.time_unix,
            data_hash=h.data_hash, square_size=h.square_size * 2,
            app_hash=h.app_hash, proposer=h.proposer,
            app_version=h.app_version, last_block_hash=h.last_block_hash,
        ),
        txs=honest.txs,
    )
    assert app.process_proposal(forged) is False


def test_blind_dah_differs_from_honest():
    """The blind tree produces a root over the swapped square that an honest
    recomputation cannot reproduce — the fraud a light client would prove."""
    rng = np.random.default_rng(3)
    app, signer, privs = make_app()
    txs = _pfb_txs(signer, privs, rng)
    res = app.prepare_proposal(txs, t=1_700_000_100.0)
    swapped = malicious.swap_first_two_blobs(res.square)
    assert not np.array_equal(swapped, res.square.ods)
    _, forged_root = malicious.blind_dah(swapped)
    assert forged_root != res.block.header.data_hash
