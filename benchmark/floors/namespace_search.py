"""The least time one chip could take for the window's namespace searches:
per batched read, the k*k leaf namespaces (29 bytes each) read once from HBM
and the queries beside them. Compares are byte-wise and bound by bytes."""


def per_search(k: int, queries: int, peaks: dict) -> tuple[float, str]:
    n_bytes = k * k * 29 + queries * 29 + queries * 12
    return n_bytes / peaks["hbm_bytes_per_s"], "bytes"


def floor_seconds(units: dict, peaks: dict) -> tuple[float, str]:
    total = 0.0
    for k, queries in units.get("namespace_reads", []):
        total += per_search(k, queries, peaks)[0]
    return total, "bytes"
