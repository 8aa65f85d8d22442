"""The least time FOUR chips could take to extend and commit a k x k square
that is sharded over them.

The work is `extend_commit.py`'s, whatever implements it. Bytes: the square
in (k*k*512), the extended square out ((2k)^2*512), the 4k axis roots (90
each) and the data root (32), each crossing HBM once. Operations: the three
parity quadrants as matrix products over the field the shard count gives —
GF(2^16) from 257 shards an axis (k > 128), k^3 * 256 symbol multiply-adds
each (a share is 256 16-bit symbols), GF(2^8) below, k^3 * 512 — two
operations apiece, against the int8 peak.

Both are laid against the peaks of CHIPS = 4 chips: the cell this floor
serves (`bigblock-k256-mesh`) runs one program across the four chips of its
host, and the trace's program seconds are a mean a chip (`reducers/xplane.
reduce_events` divides by the planes), which for a program that runs on all
four at once is its duration. The four is the cell's, stated here; a cell on
another number of chips takes a floor file of its own.

NOT in the floor: SHA-256's integer work (the NMT) and the two all-to-alls
over the chip-to-chip links, neither of which has a published peak in
`peaks.json`. So the share this gives is an upper bound on how close the
program is to what the chips could do.
"""

CHIPS = 4
GF8_MAX_K = 128     # 256 shards an axis: the last square the 8-bit code takes


def per_extend(k: int, peaks: dict) -> tuple[float, str]:
    symbols_a_share = 512 if k <= GF8_MAX_K else 256
    n_bytes = k * k * 512 + (2 * k) ** 2 * 512 + 4 * k * 90 + 32
    n_ops = 3 * k ** 3 * symbols_a_share * 2
    by_bytes = n_bytes / (CHIPS * peaks["hbm_bytes_per_s"])
    by_ops = n_ops / (CHIPS * peaks["int8_ops_per_s"])
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")


def floor_seconds(units: dict, peaks: dict) -> tuple[float, str]:
    """One extend per block of the window, at each block's own square size."""
    total, binds = 0.0, "bytes"
    for k in units.get("square_size", []):
        seconds, binds = per_extend(k, peaks)
        total += seconds
    return total, binds
