"""The least time FOUR chips could take for the window's namespace
searches over row level stacks resident and sharded over them: per read
(one query a request: `units["requests"]`), the k x k leaf namespaces of
the original square (29 B each, the level-0 mins) read once from HBM, and
the query's 29 B beside them. Compares are byte-wise and bound by bytes.

Laid against the peaks of CHIPS = 4 chips, as `namespace_gather_mesh.py`:
one program across the four chips of `bigblock-k256-ns-http`'s host (the
original square's rows lie on two of them, so the share is the lower for
it). NOT in the floor: the combination of four ints a query over the
chip-to-chip links, the query's way up and the answer's way down.
"""

CHIPS = 4
NS = 29


def floor_seconds(units: dict, peaks: dict) -> tuple[float, str]:
    k = units.get("square_size", 0)
    searches = units.get("requests", 0)
    if not k or not searches:
        return 0.0, "bytes"
    n_bytes = searches * (k * k * NS + NS)
    return n_bytes / (CHIPS * peaks["hbm_bytes_per_s"]), "bytes"
