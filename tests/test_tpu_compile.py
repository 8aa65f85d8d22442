"""The main path's device programs, compiled for a TPU v5e that is
described and not attached (the TPU compiler ships with the installed
jaxlib; nothing runs, so this proves lowering and memory fit, never
results or speed).

Interpret-mode Pallas tests cannot see what the chip's compiler refuses:
a slice off the tiling, too much fast memory, an unsupported cast, a
kernel it cannot partition across a mesh. These cases ask it directly, at
the shapes chip_smoke.py and the benchmark's cells run: the block
pipelines and the prover's level stack with the Pallas SHA-256 kernel at
k=64 / k=128 (full squares) and at k=8 / 16 / 32 (the small squares of
`k64-pfb-light`; at k=8 no hash level reaches the kernel's 1,024-message
tile, `ops/sha256.sha256` keeps to its jnp path, and the case checks that
the program holds NO kernel, as the cases with `kernels=False` all do), the
batched
secp256k1 verifier, the namespace search, the blob commitment batch, the
four-chip sharded k=256 program, and the level pass over the square that
program leaves sharded (k=128 / 256, both orientations: the case the chips
refused until PR 36, whose refusal is kept as a case too), and the gather
of a light round's cells and proof nodes out of that square and its level
stacks (PR 37: four chips at k=128 / 256, both orientations; one chip at
k=128), also at the largest bucket a combined dispatch carries, and a
namespace read's search and row gather over that square and its row
level stack (four chips at k = 128 / 256; one chip at k = 128).

Rules this file keeps (they are what lets it run under `pytest -n 6`):
the topology is described ONLY inside the module fixture (one process at
a time may load libtpu; a worker that is not handed this file must never
try), everything compiles in the test's own process, every program is a
fresh closure (a jit cache shared with the production factories would
hand a Pallas-lowered trace to a later CPU test), and the persistent
compile cache is off (an entry compiled for a described chip cannot be
read back without one).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _pallas_sha(monkeypatch):
    # the process's backend is the CPU, so ops/sha256.use_pallas() would
    # trace the jnp path and the compile would prove nothing
    monkeypatch.setenv("CELESTIA_SHA256_IMPL", "pallas")


def _compile(fn, *shapes, kernels: bool = True, **jit_kw):
    """Lower+compile a fresh closure over `fn` for the described chip;
    returns the compiled program after the checks every case shares."""
    compiled = jax.jit(lambda *a: fn(*a), **jit_kw).lower(*shapes).compile()
    mem = compiled.memory_analysis()
    resident = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes + mem.generated_code_size_in_bytes)
    assert resident < V5E_HBM_BYTES, f"{resident} bytes do not fit one v5e"
    assert ("tpu_custom_call" in compiled.as_text()) == kernels, \
        "a Pallas kernel in the compiled program: wanted %s" % kernels
    return compiled


def _u8(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=sharding)


def test_sha256_pallas_kernel(one_chip):
    """The NMT leaf hash at k=64: 4096 messages of 1+29+512 bytes."""
    from celestia_app_tpu.ops import sha256

    _compile(sha256.sha256, _u8((4096, 542), one_chip))


SQUARES = [8, 16, 32, 64, 128]
# the Pallas SHA-256 kernel takes batches of >= 1,024 messages
# (ops/sha256.sha256); a 16x16 extended square has 256 leaves an axis
PALLAS_FROM_K = 16


@pytest.mark.parametrize("k", SQUARES)
def test_block_pipeline(one_chip, k):
    """da/eds.pipeline_fn: RS extend + 4k NMT roots + data root, the
    program behind compute_entry(ods, "device")."""
    from celestia_app_tpu.da import eds

    _compile(eds.pipeline_fn(k), _u8((k, k, 512), one_chip),
             kernels=k >= PALLAS_FROM_K)


@pytest.mark.parametrize("k", SQUARES)
def test_prover_level_stack(one_chip, k):
    """BlockProver's level pass (da/proof_device._jitted_row_levels)."""
    from celestia_app_tpu.da import proof_device

    levels = proof_device._jitted_row_levels.__wrapped__(k)
    _compile(levels, _u8((2 * k, 2 * k, 512), one_chip),
             kernels=k >= PALLAS_FROM_K)


def test_namespace_search(one_chip):
    """da/namespace_device: 16 queries against a 64x64 square's leaves."""
    from celestia_app_tpu.da import namespace_device

    search = namespace_device._jitted_search.__wrapped__(64 * 64, 16)
    _compile(search, _u8((64 * 64, 29), one_chip), _u8((16, 29), one_chip),
             kernels=False)


# (blobs, bytes a blob) -> the program's shape key (rows, largest width,
# padded picks): the three produce cells' batches (the light cell's largest
# class) and chip_smoke's 64 blobs x 58 shares
COMMITMENT_BATCHES = {
    "k128-pfb-full": (36, 200_000, (16384, 8, 2048)),
    "k64-pfb-full": (36, 50_000, (4096, 2, 2048)),
    "k64-pfb-light": (16, 8_000, (512, 1, 512)),
    "chip_smoke": (64, 478 + 57 * 482, (4096, 1, 4096)),
}


@pytest.mark.parametrize("batch", COMMITMENT_BATCHES)
def test_blob_commitment_batch(one_chip, batch):
    """da/commitment_device's one program: every level 0 .. log2(width)
    over the whole buffer and the gather of the named roots. The Pallas
    SHA-256 kernel takes a level from 1,024 messages: every batch here
    but the light cell's 512 rows holds it."""
    import functools

    from celestia_app_tpu.da import commitment_device
    from celestia_app_tpu.da.blob import Blob
    from celestia_app_tpu.da.namespace import Namespace

    n_blobs, size, key = COMMITMENT_BATCHES[batch]
    buf, width, picks, _per_blob, _used = commitment_device._pack(
        [Blob(Namespace.v0(b"\x01" * 8), bytes(size))] * n_blobs, 64)
    assert (buf.shape[0], width, len(picks)) == key
    rows = key[0]
    program = functools.partial(
        commitment_device.commitment_subtree_roots.__wrapped__, width=width)
    _compile(program, _u8((rows, 512), one_chip),
             jax.ShapeDtypeStruct(picks.shape, jnp.int32, sharding=one_chip),
             kernels=rows >= 1024)


def test_secp256k1_verify_batch(one_chip):
    """One admission bucket (64 lanes). uint64 limbs are emulated on a
    32-bit vector unit, which is why this is the slow compile."""
    from celestia_app_tpu.ops import secp256k1 as k1

    n = 64
    with jax.enable_x64(True):
        def s(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        u64, i32, b = jnp.uint64, jnp.int32, jnp.bool_
        shapes = (
            s((n, k1.N_LIMBS), u64), s((n, k1.N_LIMBS), u64), s((n,), b),
            s((n, k1.N_WINDOWS), i32), s((n, k1.N_WINDOWS), i32),
            s((n, k1.N_G_WINDOWS), i32), s((n, k1.N_G_WINDOWS), i32),
            s((n,), i32), s((n,), i32),
            s((n, k1.N_LIMBS), u64), s((n, k1.N_LIMBS), u64), s((n,), b),
        )
        _compile(k1._kernel_fns(), *shapes, kernels=False)


def test_sharded_pipeline_k256_four_chips(topo):
    """parallel/sharded_eds over the four described chips at the
    big-block shape (GF(2^16)): partitions (every Pallas kernel inside
    the shard_map), rides two all-to-alls, and fits each chip."""
    from celestia_app_tpu.parallel import mesh as mesh_mod
    from celestia_app_tpu.parallel import sharded_eds

    k = 256
    mesh = mesh_mod.make_mesh(4, k=k, devices=topo.devices)
    assert dict(mesh.shape) == {"data": 1, "seq": 4}
    placed = sharded_eds.input_sharding(mesh)
    compiled = _compile(sharded_eds.sharded_pipeline_fn(mesh, k),
                        _u8((1, k, k, 512), placed), in_shardings=placed)
    text = compiled.as_text()
    assert text.count("all-to-all") >= 2
    assert "all-gather" in text


@pytest.fixture(scope="module")
def seq_mesh(topo):
    """The mesh a four-chip host runs big squares on, and the placement
    the sharded pipeline leaves an entry's square in (`eds_dev[0]` of its
    first output: rows over `seq`)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from celestia_app_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.make_mesh(4, k=128, devices=topo.devices)
    assert dict(mesh.shape) == {"data": 1, "seq": 4}
    return mesh, NamedSharding(mesh, P(mesh_mod.SEQ_AXIS))


@pytest.mark.parametrize("col", [False, True], ids=["rows", "cols"])
@pytest.mark.parametrize("k", [128, 256])
def test_level_pass_under_the_mesh_entrys_sharding(seq_mesh, k, col):
    """DeviceEntry's level pass over a square the sharded pipeline left
    split over four chips (da/proof_device._jitted_sharded_levels): the
    Pallas kernels inside the shard_map, the column orientation riding
    one all-to-all, every level left split by tree, each chip under its
    16 GiB."""
    from celestia_app_tpu.da import proof_device
    from celestia_app_tpu.parallel.mesh import SEQ_AXIS

    mesh, placed = seq_mesh
    square = _u8((2 * k, 2 * k, 512), placed)
    assert proof_device.rows_sharded_over(square) == (mesh, SEQ_AXIS)
    program = proof_device._jitted_sharded_levels.__wrapped__(
        mesh, SEQ_AXIS, k, col)
    # __wrapped__: a fresh jit each time, outside the factory's lru_cache
    compiled = _compile(program, square, in_shardings=placed)
    text = compiled.as_text()
    assert ("all-to-all" in text) == col
    levels = jax.tree.leaves(compiled.output_shardings)
    assert len(levels) == (2 * k).bit_length() * 3
    assert all(s.spec[0] == SEQ_AXIS for s in levels)


def test_plain_level_pass_is_refused_for_a_sharded_square(seq_mesh):
    """Why the entry needs the shard_map: the one-chip level pass, handed
    the same sharded square through a plain jit, is refused by the chip's
    compiler in these words (every light round of a mesh-engine height
    failed so on four chips: PERF.md, PR 35). The CPU backend has no
    Pallas kernel and would pass."""
    from celestia_app_tpu.da import proof_device

    _mesh, placed = seq_mesh
    k = 128
    levels = proof_device._jitted_row_levels.__wrapped__(k)
    with pytest.raises(NotImplementedError,
                       match="Mosaic kernels cannot be automatically "
                             "partitioned"):
        jax.jit(lambda eds: levels(eds)).lower(
            _u8((2 * k, 2 * k, 512), placed)).compile()


def _gather_shapes(k, placed, cells_at, n_cells=16):
    """The gather's arguments at size k: the square, one orientation's
    level stack below the roots (2k trees of 2k >> l nodes: min, max,
    hash) and `n_cells` cells (a light round's sixteen by default)."""
    square = _u8((2 * k, 2 * k, 512), placed)
    levels = [tuple(_u8((2 * k, (2 * k) >> level, width), placed)
                    for width in (29, 29, 32))
              for level in range((2 * k).bit_length() - 1)]
    cells = jax.ShapeDtypeStruct((2, n_cells), jnp.int32, sharding=cells_at)
    return square, levels, cells


@pytest.mark.parametrize("cells", [16, 2048])
@pytest.mark.parametrize("col", [False, True], ids=["rows", "cols"])
@pytest.mark.parametrize("k", [128, 256])
def test_sample_gather_under_the_mesh_entrys_sharding(seq_mesh, k, col,
                                                      cells):
    """A light round's sixteen cells — and the largest bucket a combined
    dispatch carries, 2,048 cells of 128 rounds — cut out of the square
    and the level stack the four chips hold (da/proof_device.
    _jitted_sharded_sample_gather): no kernel to partition, ONE
    all-reduce of the packed answer, and no gather of the square or of a
    level stack to anywhere."""
    import re

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from celestia_app_tpu.da import proof_device
    from celestia_app_tpu.parallel.mesh import SEQ_AXIS

    assert cells in (proof_device.MIN_GATHER_BUCKET,
                     proof_device.MAX_GATHER_BUCKET)
    mesh, placed = seq_mesh
    everywhere = NamedSharding(mesh, P())
    program = proof_device._jitted_sharded_sample_gather.__wrapped__(
        mesh, SEQ_AXIS, k, col)
    compiled = _compile(program,
                        *_gather_shapes(k, placed, everywhere, cells),
                        kernels=False,
                        in_shardings=(placed, placed, everywhere))
    text = compiled.as_text()
    assert len(re.findall(r" all-reduce(?:-start)?\(", text)) == 1
    assert "all-gather" not in text and "all-to-all" not in text
    shares, nodes = compiled.output_shardings
    assert shares.is_fully_replicated and nodes.is_fully_replicated
    depth = (2 * k).bit_length() - 1
    assert compiled.memory_analysis().output_size_in_bytes < \
        2 * cells * (512 + depth * 90)


def test_sample_gather_on_one_chip(one_chip):
    """The same cut over a k = 128 square one chip holds (a batched
    engine's entry): a plain jit, no collective."""
    from celestia_app_tpu.da import proof_device

    program = proof_device._jitted_sample_gather.__wrapped__(False)
    compiled = _compile(program, *_gather_shapes(128, one_chip, one_chip),
                        kernels=False)
    assert "all-reduce" not in compiled.as_text()



@pytest.mark.parametrize("k,rows", [(128, 1), (128, 128), (256, 1),
                                    (256, 128), (256, 256)])
def test_namespace_gather_under_the_mesh_entrys_sharding(seq_mesh, k, rows):
    """A namespace read's rows — one, the largest namespace of the 32 MB
    blocks' bucket (83 rows -> 128), and every row of the original square
    — with their proof nodes, cut out of the square and the row level
    stack the four chips hold (da/proof_device.
    _jitted_sharded_namespace_gather): no kernel to partition, the
    answer combined by all-reduce as u32 words, and no gather of the
    square or of a level stack to anywhere."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from celestia_app_tpu.da import proof_device
    from celestia_app_tpu.parallel.mesh import SEQ_AXIS

    assert rows in proof_device.namespace_row_buckets(k)
    mesh, placed = seq_mesh
    everywhere = NamedSharding(mesh, P())
    program = proof_device._jitted_sharded_namespace_gather.__wrapped__(
        mesh, SEQ_AXIS, k)
    square, levels, _ = _gather_shapes(k, placed, everywhere)
    index = jax.ShapeDtypeStruct((3, rows), jnp.int32, sharding=everywhere)
    compiled = _compile(program, square, levels, index, kernels=False,
                        in_shardings=(placed, placed, everywhere))
    text = compiled.as_text()
    assert "all-reduce" in text
    assert "all-gather" not in text and "all-to-all" not in text
    words, nodes = compiled.output_shardings
    assert words.is_fully_replicated and nodes.is_fully_replicated
    depth = (2 * k).bit_length() - 1
    # the answer's own bytes, its nodes padded to the device's tiles
    out = compiled.memory_analysis().output_size_in_bytes
    assert rows * k * 512 + rows * 2 * depth * 90 <= out < \
        rows * k * 512 + rows * 2 * depth * 128 + 4096


def test_namespace_search_over_the_mesh_entrys_level_stack(seq_mesh):
    """The search of one query over the level-0 mins of a k = 256 row
    level stack split over four chips (da/namespace_device.
    _jitted_sharded_search): four ints a query combined, nothing of the
    stack moved."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from celestia_app_tpu.da import namespace_device
    from celestia_app_tpu.parallel.mesh import SEQ_AXIS

    k = 256
    mesh, placed = seq_mesh
    everywhere = NamedSharding(mesh, P())
    program = namespace_device._jitted_sharded_search.__wrapped__(
        mesh, SEQ_AXIS, k)
    compiled = _compile(program, _u8((2 * k, 2 * k, 29), placed),
                        _u8((1, 29), everywhere), kernels=False,
                        in_shardings=(placed, everywhere))
    text = compiled.as_text()
    assert "all-gather" not in text and "all-to-all" not in text
    assert compiled.output_shardings.is_fully_replicated


def test_namespace_read_on_one_chip(one_chip):
    """The search and the row gather over a k = 128 square one chip holds
    (a batched engine's entry): plain jits, no collective."""
    from celestia_app_tpu.da import namespace_device, proof_device

    k = 128
    search = namespace_device._jitted_resident_search.__wrapped__(k)
    compiled = _compile(search, _u8((2 * k, 2 * k, 29), one_chip),
                        _u8((1, 29), one_chip), kernels=False)
    assert "all-reduce" not in compiled.as_text()
    gather = proof_device._jitted_namespace_gather.__wrapped__(k)
    square, levels, _ = _gather_shapes(k, one_chip, one_chip)
    index = jax.ShapeDtypeStruct((3, 64), jnp.int32, sharding=one_chip)
    compiled = _compile(gather, square, levels, index, kernels=False)
    assert "all-reduce" not in compiled.as_text()
