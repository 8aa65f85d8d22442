"""What a light node's samples of one height must be, by the plain
reference: the square rebuilt from the block's raw txs (`plain_da.
commit_block`), its axis roots and data root, and each sampled cell's share
and single-leaf NMT proof nodes, read off the row trees' levels instead of
rehashed a proof at a time. numpy + hashlib; nothing of the program
imported.

`check_height` is one height's whole comparison, so that a serving cell can
run its heights in processes of their own (a 256 x 256 square is ≈ 6 s of
reference on one core).
"""

from __future__ import annotations

from reference import plain_da as da


def row_levels(eds, row: int) -> list[list[da.Node]]:
    """Every node of one row's NMT, leaves first (2k is a power of two, so
    each level halves the one below)."""
    width = eds.shape[0]
    k = width // 2
    level = []
    for c in range(width):
        share = eds[row, c].tobytes()
        level.append(da.nmt_leaf(
            share[:da.NS] if row < k and c < k else da.PARITY_NS, share))
    out = [level]
    while len(level) > 1:
        level = [da.nmt_inner(level[i], level[i + 1])
                 for i in range(0, len(level), 2)]
        out.append(level)
    return out


def proof_nodes(levels: list[list[da.Node]], leaf: int) -> list[bytes]:
    """The single-leaf range proof's nodes in the order `plain_node.
    prove_range` gives them (the out-of-range subtree roots, left to
    right)."""
    nodes: list[bytes] = []

    def walk(lo: int, hi: int) -> None:
        if hi <= leaf or lo > leaf:
            width = hi - lo
            nodes.append(b"".join(
                levels[width.bit_length() - 1][lo // width]))
        elif hi - lo > 1:
            mid = (lo + hi) // 2
            walk(lo, mid)
            walk(mid, hi)

    walk(0, len(levels[0]))
    return nodes


def check_height(txs: list[bytes], max_k: int, kept: list) -> dict:
    """The reference's commitments of one block and, over `kept` — [(cells,
    samples)], a sample None where the server refused it, else (share,
    proof nodes) — the count of shares and of proofs whose nodes differ
    from the reference's."""
    ref = da.commit_block(txs, max_k)
    eds = ref["eds"]
    levels: dict[int, list] = {}
    shares_bad = nodes_bad = 0
    for cells, samples in kept:
        for (row, col), sample in zip(cells, samples):
            if sample is None:
                continue
            share, nodes = sample
            shares_bad += share != eds[row, col].tobytes()
            if row not in levels:
                levels[row] = row_levels(eds, row)
            nodes_bad += nodes != proof_nodes(levels[row], col)
    return {"data_root": ref["data_root"],
            "digest": da.sha256(b"".join(ref["row_roots"]
                                         + ref["col_roots"])),
            "shares_bad": shares_bad, "nodes_bad": nodes_bad}
