"""The least time one chip could take to extend and commit a k x k square.

Bytes: the square in (k*k*512), the extended square out ((2k)^2*512), the
4k axis roots (90 each) and the data root (32), each crossing HBM once.
Operations: the three parity quadrants as GF(2^8) matrix products, k^3*512
multiply-adds each, two operations apiece, against the int8 peak. The floor
is the larger of the two times. SHA-256's integer work (the NMT) has no
published peak on this chip and is NOT in the floor, so the share this gives
is an upper bound on how close the program is to what the chip could do.
"""


def per_extend(k: int, peaks: dict) -> tuple[float, str]:
    n_bytes = k * k * 512 + (2 * k) ** 2 * 512 + 4 * k * 90 + 32
    n_ops = 3 * k ** 3 * 512 * 2
    by_bytes = n_bytes / peaks["hbm_bytes_per_s"]
    by_ops = n_ops / peaks["int8_ops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")


def floor_seconds(units: dict, peaks: dict) -> tuple[float, str]:
    """One extend per block of the window, at each block's own square size."""
    total, binds = 0.0, "bytes"
    for k in units.get("square_size", []):
        seconds, binds = per_extend(k, peaks)
        total += seconds
    return total, binds
