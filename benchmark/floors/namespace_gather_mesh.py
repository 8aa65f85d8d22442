"""The least time FOUR chips could take to cut the window's namespace reads
— each read's rows of shares and the proof nodes of their ranges — out of
squares and row level stacks resident and sharded over them.

One gather a present read, its touched rows padded to the program's bucket
(a power of two: `units["ns_rows_padded"]`). A padded row is the original
half of a row of the square, k shares of 512 B; each row brings the
sibling nodes of the paths of its first and last column, log2(2k) a path,
90 B each (min namespace, max namespace, digest: `units["ns_nodes"]`).
Each byte is read from HBM once and written once:
2 x (rows x k x 512 + nodes x 90) bytes. Bound by bytes — there are no
operations to speak of.

Laid against the peaks of CHIPS = 4 chips, as `sample_gather_mesh.py`: the
cell this floor serves (`bigblock-k256-ns-http`) runs the gather as one
program across the four chips of its host, and the trace's program seconds
are a mean a chip, which for such a program is its duration.

NOT in the floor: the all-reduce of the answer over the chip-to-chip links
(no published peak in `peaks.json`), the index upload and the answer's way
down, and an absent read's successor leaf (a one-cell sample gather,
`jit_mesh_sample_gather`, outside this program). So the share is an upper
bound on how close the program is to what the chips could do.
"""

CHIPS = 4
SHARE = 512
NODE = 90


def floor_seconds(units: dict, peaks: dict) -> tuple[float, str]:
    k = units.get("square_size", 0)
    rows = units.get("ns_rows_padded", 0)
    if not k or not rows:
        return 0.0, "bytes"
    n_bytes = 2 * (rows * k * SHARE + units.get("ns_nodes", 0) * NODE)
    return n_bytes / (CHIPS * peaks["hbm_bytes_per_s"]), "bytes"
