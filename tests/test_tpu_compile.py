"""The served k-hop traversal compiles for the chip at the width it
has in the `graph500-khop.khop-deep-c16` cell, its sharded form for
four chips at the width of `graph500-khop-x4.khop-deep-c16`, and the
served shortest path's search and walk at the width of
`pokec-shortest.pairs-c16`, and the vector scan's lanes program at the
width of `sift1m-exact.knn-mix`: the TPU's compiler is
installed here and compiles for a v5e that is described, not attached
(nothing runs, so this says nothing about results or times). It is
what interpret mode cannot show: whether Mosaic takes the hub rows'
kernel (`ops/bitgraph._hub_kernel`) as written, and whether a call
fits the fast memory it asks for.

Every compile for the chip lives in THIS file, behind a fixture: one
process at a time may load the TPU's library, and only the worker that
is given this file does."""

import jax
import jax.numpy as jnp
import pytest

from dgraph_tpu.ops import bitgraph

# the cell's shapes (PERF.md section 4): vertices, hub rows and their
# width in words, and the four gathered classes' rows
N, ROWS = 174_080, 69_658
WORDS = bitgraph.hub_row_words(N)
GATHERED = ((60_000, 1), (22_000, 2), (9_000, 3), (5_000, 4))


# the four-chip cell's shapes at SCALE 20 (PERF.md section 4; the
# generator's seed 3700000011): vertices and vertices with an in-edge,
# hub rows (the classes from 32 in-edges up) and the nine gathered
# classes' (rows, in-edges)
N4, COVERED4, ROWS4 = 646_960, 547_110, 80_938
GATHERED4 = ((145_952, 1), (73_625, 2), (44_967, 3), (30_705, 4),
             (46_064, 6), (36_127, 8), (30_177, 12), (11_542, 16),
             (47_013, 24))
CHIPS = 4

# the cell `pokec-shortest.pairs-c16` at scale 10 (the generator's seed
# 3700004501, counted on the CPU): vertices and vertices with an
# out-edge (the TRANSPOSED tile's rows), hub rows (the classes from 5
# out-edges up, 2.13 GB of the 2 GiB budget) and the four gathered
# classes' (rows, out-edges)
NP, COVEREDP, ROWSP = 157_022, 152_787, 106_688
GATHEREDP = ((12_815, 1), (12_346, 2), (11_194, 3), (9_744, 4))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache and cannot be
    # read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_the_hub_rows_kernel_compiles_at_the_cells_width(one_chip):
    """With the plan of needed tiles as its second prefetched scalar
    operand, read by the rows' index map: whether Mosaic takes a
    block index that comes out of SMEM."""
    lanes, tile = bitgraph.LANES, bitgraph._HUB_TILE_ROWS
    compiled = jax.jit(
        lambda dense, fw, active, plan: bitgraph._hub_call(
            dense, fw, active, plan, lanes, tile)).lower(
        _shape(one_chip, (ROWS, WORDS), jnp.uint32),
        _shape(one_chip, (lanes, WORDS), jnp.uint32),
        _shape(one_chip, (), jnp.uint32),
        _shape(one_chip, (2 * -(-ROWS // tile),), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows,words,seeds", (
    (ROWS, WORDS, 8), (ROWS, WORDS, 32),
    (bitgraph._chip_rows(ROWS4, CHIPS), bitgraph.hub_row_words(N4), 8)),
    ids=("khop", "khop-32-seeds", "khop-x4-a-chip"))
def test_the_columns_kernel_compiles_at_the_cells_widths(
        one_chip, rows, words, seeds):
    """With a seed's block of columns as a prefetched scalar that the
    rows' index map reads on the MINOR axis, and the seeds as the
    grid's inner axis over an output block that stands still."""
    compiled = jax.jit(bitgraph._columns_call).lower(
        _shape(one_chip, (rows, words), jnp.uint32),
        *[_shape(one_chip, (seeds,), jnp.int32)] * 2,
        *[_shape(one_chip, (seeds,), jnp.uint32)] * 2).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_the_whole_traversal_compiles_with_the_kernel_in_it(
        one_chip, monkeypatch):
    # the program asks which backend it is traced for; here that is
    # the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lanes = bitgraph.LANES
    covered = ROWS + sum(rows for rows, _ in GATHERED)
    compiled = bitgraph.bfs_traverse.lower(
        [_shape(one_chip, g, jnp.int32) for g in GATHERED],
        _shape(one_chip, (ROWS, WORDS), jnp.uint32),
        _shape(one_chip, (2 * 8 + lanes,), jnp.int32),
        n_slots=N, n_covered=covered, lanes=lanes).compile()
    # eight seed slots at this width: the first level reads their
    # columns (a branch of the loop's body, the columns' kernel in
    # it), the others stream (the rows' kernel)
    assert bitgraph.columns_cheaper(
        8, ROWS, WORDS, sum(m * d for m, d in GATHERED))
    assert " conditional(" in compiled.as_text()
    assert compiled.as_text().count("tpu_custom_call") == 2
    mem = compiled.memory_analysis()
    # the rows are an argument; what a call adds is lane state and the
    # kernel's per-row words, far under a chip's 16 GB
    assert mem.argument_size_in_bytes >= 4 * ROWS * WORDS
    assert mem.temp_size_in_bytes < 256 << 20


def test_the_path_search_compiles_with_its_walk_at_the_pokec_cells_width(
        one_chip, monkeypatch):
    """`bfs_paths` as a call of `pokec-shortest.pairs-c16` reaches it:
    eight pairs, depth 15, the transposed tile of a tenth of Pokec:
    the k-hop loop's two kernels (the first level reads the eight
    targets' columns) with a level a lane and slot kept, then the
    walk's loop; what a call adds to the rows is 32 B a vertex of
    levels and lane state."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lanes, words = bitgraph.LANES, bitgraph.hub_row_words(NP)
    assert sum(m for m, _ in GATHEREDP) + ROWSP == COVEREDP
    assert 4 * ROWSP * words <= 2 << 30           # the tile budget
    compiled = bitgraph.bfs_paths.lower(
        [_shape(one_chip, g, jnp.int32) for g in GATHEREDP],
        _shape(one_chip, (ROWSP, words), jnp.uint32),
        _shape(one_chip, (NP,), jnp.uint32),
        _shape(one_chip, (2 * 8 + 2 * lanes,), jnp.int32),
        n_slots=NP, n_covered=COVEREDP, lanes=lanes,
        width=bitgraph.path_width(15, NP)).compile()
    assert bitgraph.path_width(15, NP) == 16
    assert bitgraph.columns_cheaper(
        8, ROWSP, words, sum(m * d for m, d in GATHEREDP))
    text = compiled.as_text()
    assert " conditional(" in text
    assert text.count("tpu_custom_call") == 2
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 4 * ROWSP * words
    print(f"bfs_paths temp bytes: {mem.temp_size_in_bytes}")
    assert mem.temp_size_in_bytes < 256 << 20


def test_the_vector_scans_lanes_program_compiles_at_the_knn_cells_width(
        one_chip):
    """`jit__topk_device_jit` as a call of `sift1m-exact.knn-mix` with
    a `knn100` aboard reaches it: eight lanes over the 500,000 x 128
    block, a mask a lane, largest k 100 (three candidates a bucket);
    what a call adds to the block is the lanes' score rows."""
    from dgraph_tpu.ops import knn
    n, k = 500_000, 100
    n_pad = knn.padded_rows(n)
    compiled = knn._topk_device_jit.lower(
        _shape(one_chip, (n_pad, 128), jnp.float32),
        _shape(one_chip, (knn.LANES, 128), jnp.float32),
        tuple(_shape(one_chip, (n_pad,), jnp.bool_)
              for _ in range(knn.LANES)),
        _shape(one_chip, (knn.LANES,), jnp.int32),
        k=k, metric="euclidean", two_stage=True,
        l_per_bucket=knn.plan_two_stage(n, k), n_real=n).compile()
    assert knn.plan_two_stage(n, k) == 3
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 4 * n_pad * 128
    # ONE small array leaves the device: indices, scores' bits, flag
    assert mem.output_size_in_bytes <= 2 * 4 * knn.LANES * (2 * k + 1)
    print(f"_topk_device_jit temp bytes: {mem.temp_size_in_bytes}")
    assert mem.temp_size_in_bytes < 256 << 20


def test_the_sharded_traversal_compiles_for_four_chips_at_the_x4_cells_width(
        topo, monkeypatch):
    """`bfs_traverse_sharded` for the described v5e:2x2 as
    `alpha --chips 4` lays SCALE 20 out: a chip's program holds ONE
    kernel and ONE collective, and asks for little beside its rows."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert len(topo.devices) == CHIPS
    mesh = Mesh(np.asarray(topo.devices), (bitgraph.SHARD_AXIS,))
    rows, whole = NamedSharding(mesh, P(bitgraph.SHARD_AXIS)), \
        NamedSharding(mesh, P())
    lanes, words = bitgraph.LANES, bitgraph.hub_row_words(N4)
    held = [-(-m // CHIPS) for m, _ in GATHERED4]
    chip_rows = bitgraph._chip_rows(ROWS4, CHIPS)
    assert chip_rows * 4 * words <= 2 << 30       # a chip's tile budget
    part_rows = tuple((m, h) for (m, _), h in zip(GATHERED4, held)) \
        + ((ROWS4, chip_rows),)
    assert sum(m for m, _ in part_rows) == COVERED4
    compiled = bitgraph.bfs_traverse_sharded.lower(
        [jax.ShapeDtypeStruct((CHIPS * h, d), jnp.int32, sharding=rows)
         for h, (_, d) in zip(held, GATHERED4)],
        jax.ShapeDtypeStruct((CHIPS * chip_rows, words), jnp.uint32,
                             sharding=rows),
        jax.ShapeDtypeStruct((2 * 8 + lanes,), jnp.int32, sharding=whole),
        mesh=mesh, part_rows=part_rows, n_slots=N4, lanes=lanes).compile()
    text = compiled.as_text()
    # the first level from the seeds' columns of a chip's own rows, a
    # branch of the loop's body with the columns' kernel in it, beside
    # the rows' kernel: the level's collective stays the one
    assert bitgraph.columns_cheaper(
        8, chip_rows, words, sum(h * d for h, (_, d) in zip(held, GATHERED4)))
    assert " conditional(" in text
    assert text.count("tpu_custom_call") == 2
    collectives = [ln for ln in text.splitlines() if any(
        f" {op}(" in ln or f" {op}-start(" in ln
        for op in ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute", "reduce-scatter"))]
    assert len(collectives) == 1, collectives
    mem = compiled.memory_analysis()
    # a chip's arguments are ITS run of the rows, not the whole block
    assert 4 * chip_rows * words <= mem.argument_size_in_bytes \
        < 2 * 4 * chip_rows * words
    # lane state, the frontier in the rows' layout and the kernel's
    # words a row: what a call adds to a chip (noted: PERF.md section 5)
    print(f"x4 temp bytes a chip: {mem.temp_size_in_bytes}")
    assert mem.temp_size_in_bytes < 512 << 20
