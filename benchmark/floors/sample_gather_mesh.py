"""The least time FOUR chips could take to cut the window's sampled cells,
and the sibling nodes of their proofs, out of squares and level stacks
resident and sharded over them.

One gather a sample request, its cells padded to the program's bucket (a
power of two, at least 16: `units["gather_cells_padded"]`). A padded cell is
its share (512 B) and the log2(2k) sibling nodes of its path (90 B each:
min namespace, max namespace, digest); each is read from HBM once and
written once: 2 x cells x (512 + log2(2k) x 90) bytes. Bound by bytes —
there are no operations to speak of.

Laid against the peaks of CHIPS = 4 chips, as `extend_commit_mesh.py`: the
cell this floor serves (`bigblock-k256-das-http`) runs the gather as one
program across the four chips of its host, and the trace's program seconds
are a mean a chip, which for such a program is its duration.

NOT in the floor: the one all-reduce of the packed answer over the
chip-to-chip links (no published peak in `peaks.json`), the index upload
and the answer's way down. So the share is an upper bound on how close the
program is to what the chips could do.
"""

CHIPS = 4
SHARE = 512
NODE = 90


def floor_seconds(units: dict, peaks: dict) -> tuple[float, str]:
    k = units.get("square_size", 0)
    cells = units.get("gather_cells_padded", 0)
    if not k or not cells:
        return 0.0, "bytes"
    path = (2 * k).bit_length() - 1             # log2(2k) siblings a cell
    n_bytes = 2 * cells * (SHARE + path * NODE)
    return n_bytes / (CHIPS * peaks["hbm_bytes_per_s"]), "bytes"
