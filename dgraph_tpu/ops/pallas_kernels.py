"""Pallas TPU kernels for the traversal hot path.

The batched BFS level (ops/bitgraph.make_bfs_bits_batched) is a
row-gather + OR-reduce: for every adjacency row r with in-neighbors
nb[r, 0..D), OR the frontier bitmap rows f[nb[r, d]] together. Under
XLA this is D separate gathers; the Pallas version maps it onto the
TPU memory system directly with the scalar-prefetch pattern
(pallas_guide: PrefetchScalarGridSpec): the in-neighbor indices are
prefetched to SMEM, the BlockSpec index_map uses them to DMA exactly
the frontier row each grid step needs HBM->VMEM, and the kernel is a
single VPU OR into the output row accumulated across the degree axis
(TPU grids execute sequentially, so revisiting the same output block
accumulates).

Every kernel compiles through Mosaic or raises: `interpret=True`
(the Pallas TPU simulator) is something only tests pass, explicitly —
a backend's name never turns it on. Callers must pad the word axis W
to a multiple of 128 (lane width).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INTERPRET_ON = pltpu.InterpretParams()

# Max int32 scalar-prefetch elements one kernel instance can hold in
# SMEM (v5e: 2^17 passes, 2^18 fails the Mosaic compile). Buckets whose
# flattened in-neighbor table exceeds this are split across calls.
SMEM_IDX_CAPACITY = 1 << 17


def bucket_or_pallas(f: jax.Array, in_nb: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """OR of gathered frontier rows: f uint32[N+1, W], in_nb
    int32[M, D] -> uint32[M, W] where out[m] = OR_d f[in_nb[m, d]].
    Rows that pad with the dummy slot index N contribute zeros exactly
    like the XLA path (f's last row is the always-empty dummy)."""
    m, d = in_nb.shape
    w = f.shape[1]
    if w % 128 != 0:
        raise ValueError(f"W={w} must be a multiple of 128 lanes")

    def kernel(idx_ref, f_row, out_ref):
        del idx_ref  # consumed by the index_map, not the body
        step = pl.program_id(1)

        @pl.when(step == 0)
        def _init():
            out_ref[...] = f_row[...]

        @pl.when(step != 0)
        def _acc():
            out_ref[...] = out_ref[...] | f_row[...]

    # Mosaic requires a block's last-two dims to be (8k, 128k)-divisible
    # OR equal to the array's own trailing dims; a (1, W) block over a
    # 2-D [N, W] array violates the sublane rule. Lift to [N, 1, W] so
    # the (1, 1, W) block's trailing dims exactly match the array.
    f3 = f[:, None, :]

    def one_call(nb_chunk: jax.Array) -> jax.Array:
        cm, cd = nb_chunk.shape
        # the prefetched index vector lives in SMEM: it must be FLAT
        # (2-D scalar arrays fail Mosaic above ~1k rows) and within
        # capacity (2^17 int32 ≈ 512 KiB, measured on v5e — larger
        # buckets are chunked below)
        flat_idx = nb_chunk.reshape(-1)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(cm, cd),
            in_specs=[
                pl.BlockSpec((1, 1, w),
                             lambda i, j, idx: (idx[i * cd + j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, w), lambda i, j, idx: (i, 0, 0)),
        )
        out = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((cm, 1, w), jnp.uint32),
            interpret=_INTERPRET_ON if interpret else False,
        )(flat_idx, f3)
        return out[:, 0, :]

    def dispatch(nb: jax.Array) -> jax.Array:
        cm, cd = nb.shape
        if cm * cd <= SMEM_IDX_CAPACITY:
            return one_call(nb)
        if cd > SMEM_IDX_CAPACITY:
            # mega-hub rows: one row's in-neighbors alone overflow
            # SMEM — split the degree axis and OR the partial
            # expansions (OR is associative, padding rows stay
            # all-zero through every part)
            acc = None
            for s in range(0, cd, SMEM_IDX_CAPACITY):
                p = dispatch(nb[:, s:s + SMEM_IDX_CAPACITY])
                acc = p if acc is None else acc | p
            return acc
        rows_per = max(1, SMEM_IDX_CAPACITY // cd)
        return jnp.concatenate([one_call(nb[s:s + rows_per])
                                for s in range(0, cm, rows_per)])

    return dispatch(in_nb)


# -- MIPS scoring tile kernel (ops/knn.py similar_to data plane) -------------

# corpus rows per MXU tile: (SCORE_TILE_N, d) corpus block + (b, d)
# queries + (b, SCORE_TILE_N) out must fit VMEM; at d = 1024 f32 this
# is ~2.5 MiB, comfortably inside the ~16 MiB/core budget
SCORE_TILE_N = 512


def score_dot_pallas(corpus: jax.Array, queries: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """Tiled (b, d) x (d, n) -> (b, n) float32 dot scores on the MXU:
    grid over n-axis tiles, each step DMAs one (TILE, d) corpus block
    HBM->VMEM, the queries stay resident, one jnp.dot per tile. This is
    the TPU-KNN scoring matmul written as an explicit Pallas pipeline
    (pallas_guide: Grid and Block Specifications); the XLA path in
    ops/knn._score_device emits the same contraction — callers opt in
    via use_pallas (same convention as bucket_or_pallas)."""
    n, d = corpus.shape
    b = queries.shape[0]
    if n % SCORE_TILE_N != 0:
        raise ValueError(
            f"corpus rows {n} must be a multiple of {SCORE_TILE_N} "
            "(ops/knn pads)")

    def kernel(c_ref, q_ref, out_ref):
        # HIGHEST for the same reason as ops/knn._score_device: this
        # kernel feeds the exact tier, and Mosaic's default float32
        # dot is one bfloat16 pass (v5e: 1.2e-3 of |q||c| off the
        # float64 dot)
        out_ref[...] = jnp.dot(q_ref[...], c_ref[...].T,
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)

    return pl.pallas_call(
        kernel,
        grid=(n // SCORE_TILE_N,),
        in_specs=[
            pl.BlockSpec((SCORE_TILE_N, d), lambda i: (i, 0)),
            pl.BlockSpec((b, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((b, SCORE_TILE_N), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=_INTERPRET_ON if interpret else False,
    )(corpus, queries)


def score_int8_pallas(codes: jax.Array, queries: jax.Array,
                      interpret: bool = False) -> jax.Array:
    """Dequant-and-dot tile kernel for the quantized ANN tier
    (ops/ivf.py): int8 residual codes (n, d) x float32 queries (b, d)
    -> (b, n) float32 approximate dots. Same pipeline shape as
    score_dot_pallas — one (TILE, d) codes block DMAd HBM->VMEM per
    grid step, queries resident — with the int8 -> f32 convert fused
    into the tile so the MXU contraction reads the narrow form
    straight out of VMEM (TPU-KNN's peak-FLOP/s recipe at a quarter
    of the HBM traffic). Per-row dequant scales and the centroid dot
    term are rank-1 postprocessing the caller applies. XLA parity
    fallback: score_int8_xla."""
    n, d = codes.shape
    b = queries.shape[0]
    if n % SCORE_TILE_N != 0:
        raise ValueError(
            f"code rows {n} must be a multiple of {SCORE_TILE_N} "
            "(ops/ivf pads)")

    def kernel(c_ref, q_ref, out_ref):
        tile = c_ref[...].astype(jnp.float32)
        out_ref[...] = jnp.dot(q_ref[...], tile.T,
                               preferred_element_type=jnp.float32)

    return pl.pallas_call(
        kernel,
        grid=(n // SCORE_TILE_N,),
        in_specs=[
            pl.BlockSpec((SCORE_TILE_N, d), lambda i: (i, 0)),
            pl.BlockSpec((b, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((b, SCORE_TILE_N), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=_INTERPRET_ON if interpret else False,
    )(codes, queries)


@jax.jit
def score_int8_xla(codes: jax.Array, queries: jax.Array) -> jax.Array:
    """The jitted XLA contraction score_int8_pallas must match
    bit-for-bit semantics-wise — CPU-parity fallback and the
    differential oracle for the tile kernel."""
    return jnp.dot(queries, codes.astype(jnp.float32).T,
                   preferred_element_type=jnp.float32)




# -- bitmap word-AND kernel (ops/setops compressed block plane) --------------

# bitmap blocks per grid step: each step ANDs one (TILE_B, W) slab of
# uint32 words in VMEM; W = 2048 uint32 lanes per 2^16-uid block (the
# uint64 bitmap split into two 32-bit lanes — TPUs have no 64-bit
# integer ALU), a multiple of the 128-lane VPU width
BITMAP_TILE_B = 8


def bitmap_and_pallas(a: jax.Array, b: jax.Array,
                      interpret: bool = False) -> jax.Array:
    """Elementwise AND of two stacked bitmap word matrices
    (uint32[B, W], W % 128 == 0): the compressed intersection's dense
    inner loop as an explicit VPU pipeline — each grid step DMAs one
    block row pair HBM->VMEM and ANDs it in one vector op (the SIMD
    bitmap-intersection kernel of "SIMD Compression and the
    Intersection of Sorted Integers", PAPERS.md).  Callers opt in via
    use_pallas (setops.bitmap_and_device), same convention as
    score_dot_pallas."""
    bsz, w = a.shape
    if w % 128 != 0:
        raise ValueError(f"W={w} must be a multiple of 128 lanes")
    if a.shape != b.shape:
        raise ValueError(f"shape mismatch {a.shape} vs {b.shape}")
    tile = BITMAP_TILE_B
    pad = (-bsz) % tile
    if pad:
        a = jnp.concatenate(
            [a, jnp.zeros((pad, w), jnp.uint32)])
        b = jnp.concatenate(
            [b, jnp.zeros((pad, w), jnp.uint32)])

    def kernel(a_ref, b_ref, out_ref):
        out_ref[...] = a_ref[...] & b_ref[...]

    out = pl.pallas_call(
        kernel,
        grid=((bsz + pad) // tile,),
        in_specs=[
            pl.BlockSpec((tile, w), lambda i: (i, 0)),
            pl.BlockSpec((tile, w), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz + pad, w), jnp.uint32),
        interpret=_INTERPRET_ON if interpret else False,
    )(a, b)
    return out[:bsz]
