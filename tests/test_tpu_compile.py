"""The served k-hop traversal compiles for the chip at the width it
has in the `graph500-khop.khop-deep` cell: the TPU's compiler is
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


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache and cannot be
    # read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_the_hub_rows_kernel_compiles_at_the_cells_width(one_chip):
    lanes = bitgraph.LANES
    compiled = jax.jit(
        lambda dense, fw, active: bitgraph._hub_call(
            dense, fw, active, lanes)).lower(
        _shape(one_chip, (ROWS, WORDS), jnp.uint32),
        _shape(one_chip, (lanes, WORDS), jnp.uint32),
        _shape(one_chip, (), jnp.uint32)).compile()
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
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    # the rows are an argument; what a call adds is lane state and the
    # kernel's per-row words, far under a chip's 16 GB
    assert mem.argument_size_in_bytes >= 4 * ROWS * WORDS
    assert mem.temp_size_in_bytes < 256 << 20
