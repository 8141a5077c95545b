"""Benchmark: 3-hop BFS traversal over a synthetic social graph at
reference scale (21M edges over 2M nodes — the shape of the
reference's systest/21million acceptance regime).

This measures the north-star data plane (BASELINE.md): multi-hop
frontier expansion — posting-list decode + merge + dedup — which in the
reference is worker/task.go:581's per-uid loop + algo.MergeSorted heaps
under query/recurse.go. The 21-million-RDF movie dataset is not
fetchable in this environment (zero egress), so the graph is a
synthetic scale-free graph of comparable shape (power-law out-degrees,
~10 avg degree).

Baseline: the same traversal in single-core vectorized NumPy over CSR —
a faithful (and generous: NumPy's C loops beat Go's heap merges) stand-in
for the reference's CPU path, which cannot be built here (Go module
downloads need network).

Device path: the core-space digest kernel
(ops/bitgraph.make_bfs_digest_batched). One device pass answers
BENCH_BATCH bit-packed queries; only an int32[B, 8] seed-slot matrix
crosses the host link per batch (the frontier bitmap is scatter-built
on device), level 1 gathers the full adjacency, and deeper levels run
in covered-slot space — ~3.7x less bitmap HBM and ~3.7x fewer gather
descriptors on this graph, which is what lets the batch stay wide at
21M edges (round-2's ceiling: per-level [N+1, W] bitmaps capped
BENCH_BATCH at 8192 on a 16GB chip).

The backend comes up first, before the expensive graph build: the
platform is whatever JAX_PLATFORMS says, else the chip, and no chip is
an error (utils/backend.require_devices). Any failure exits non-zero.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N}
vs_baseline = device_QPS / baseline_QPS where the baseline runs the
same queries one at a time on the CPU (>1 means higher throughput).

Timing notes: every timed dispatch gets its own seed matrix. Each run
blocks on the per-level popcount checksums, paying one dispatch
round-trip per sync; with BENCH_PIPE batches in flight that fixed cost
amortizes like a serving system's request pipeline.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

from dgraph_tpu.bench.bfsgraph import csr_to_dict, make_graph, numpy_bfs

N_NODES = int(os.environ.get("BENCH_NODES", 2_000_000))
N_EDGES = int(os.environ.get("BENCH_EDGES", 21_000_000))
# Queries answered per device pass (W = BATCH/32 words per bitmap row).
# The gather unit is descriptor-rate bound, so QPS scales ~linearly
# with BATCH until bitmap memory caps it; the memory guard below halves
# BATCH until the estimated footprint fits HBM.
BATCH = int(os.environ.get("BENCH_BATCH", 24576))
SEEDS = 8                                          # seed uids per query
DEPTH = 3
RUNS = 7
BASE_RUNS = 32
# batches dispatched per sync: the dispatch round-trip is paid once
# per sync, so sustained throughput — what a serving system sees with
# requests in flight — times PIPE dispatched batches per readback
PIPE = int(os.environ.get("BENCH_PIPE", 3))
HBM_BYTES = int(float(os.environ.get("BENCH_HBM_GB", 16)) * 2**30)


def init_backend():
    """Initialize the jax backend before any expensive work: place the
    compile cache, then take the devices. The platform is whatever
    JAX_PLATFORMS says, else the chip; no chip raises
    (utils/backend.NoAcceleratorError) — there is no CPU fallback.
    Returns (devices, platform)."""
    from dgraph_tpu.utils.backend import (
        configure_compile_cache, require_devices,
    )

    configure_compile_cache()
    devs = require_devices()
    return devs, devs[0].platform


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--pallas", action="store_true",
        help="route the per-bucket gather-OR through the scalar-"
             "prefetch Pallas kernel (ops/pallas_kernels."
             "bucket_or_pallas) instead of the XLA gather path; "
             "requires the query batch to be a multiple of 4096 so "
             "the bitmap word axis is 128-lane aligned. A kernel "
             "that fails to build fails the run.")
    return ap.parse_args()


def main():
    args = parse_args()
    devs, platform = init_backend()
    on_accel = platform != "cpu"
    sys.stderr.write(f"jax devices: {devs} (platform={platform})\n")

    t0 = time.time()
    uniq_src, indptr, dst = make_graph(N_NODES, N_EDGES)
    n_edges = len(dst)
    sys.stderr.write(f"graph: {len(uniq_src)} srcs, {n_edges} edges "
                     f"({time.time()-t0:.1f}s)\n")

    if args.pallas and not on_accel:
        # the Pallas interpreter is not the kernel: nothing it does
        # on a CPU is worth a metric line
        raise SystemExit("--pallas needs the chip (platform is cpu)")
    batch = BATCH if on_accel else 256  # JAX_PLATFORMS=cpu: CI-sized
    pipe = PIPE if on_accel else 1
    runs = RUNS if on_accel else 2

    # one seed matrix per dispatch: matrix 0 warms + parity-checks, the
    # rest feed the timed runs
    rng = np.random.default_rng(1)
    n_mats = runs * pipe + 1
    seed_mat = np.sort(uniq_src[rng.integers(
        0, len(uniq_src), (n_mats * batch, SEEDS))], axis=1)  # uint64

    # ---- CPU baseline: one query at a time, like a per-request
    # goroutine in the reference ----
    base_times = []
    base_counts = []
    for i in range(min(BASE_RUNS, batch)):
        t = time.perf_counter()
        c = numpy_bfs(uniq_src, indptr, dst, np.unique(seed_mat[i]), DEPTH)
        base_times.append(time.perf_counter() - t)
        base_counts.append(c)
    base_p50 = float(np.median(base_times)) * 1e3
    base_qps = 1e3 / base_p50
    sys.stderr.write(f"numpy baseline p50 {base_p50:.3f} ms/query = "
                     f"{base_qps:.0f} QPS; counts {base_counts[:8]}\n")

    # ---- device path: core-space digest kernel ----
    import jax
    import jax.numpy as jnp

    from dgraph_tpu.ops.bitgraph import (
        build_bitadjacency, build_core_adjacency,
        make_bfs_digest_batched, make_frontier_counts_batched,
        uid_lists_to_seed_slots,
    )

    t0 = time.time()
    edges = csr_to_dict(uniq_src, indptr, dst)
    badj = build_bitadjacency(edges)
    core = build_core_adjacency(badj)
    padded = sum(b.in_nb.shape[0] * b.degree for b in badj.buckets)
    cpad = sum(b.in_nb.shape[0] * b.degree for b in core.buckets)
    adj_bytes = 4 * (padded + cpad) + 4 * core.n_core
    sys.stderr.write(
        f"adjacency built ({time.time()-t0:.1f}s): slots={badj.n_slots} "
        f"covered={badj.n_covered} ({badj.n_covered/badj.n_slots:.0%}) "
        f"full_padded={padded} core_padded={cpad} "
        f"({cpad/max(padded,1):.0%} of gathers after level 1)\n")

    # memory guard: the level-1 boundary holds the full seed bitmap,
    # the slot-space reach, and the two row-space bitmaps; deeper
    # levels hold 3 row-space arrays. Allow ~2.5GB scheduling slack —
    # the XLA allocator fragments (measured 47% at the 32768 OOM).
    while batch > 1024:
        W = (batch + 31) // 32
        need = ((badj.n_slots + 1) * W * 4
                + 3 * (badj.n_covered + 1) * W * 4
                + adj_bytes + (5 << 29))
        if need <= HBM_BYTES:
            break
        sys.stderr.write(f"batch {batch} needs ~{need>>30}GiB; halving\n")
        batch //= 2

    t0 = time.time()
    slot_mats = []
    for m in range(n_mats):
        rows = seed_mat[m * batch:(m + 1) * batch]
        slot_mats.append(jax.device_put(jnp.asarray(
            uid_lists_to_seed_slots(badj, list(rows), SEEDS))))
    sys.stderr.write(f"packed {n_mats} seed matrices of {batch} queries "
                     f"({time.time()-t0:.1f}s, "
                     f"{slot_mats[0].nbytes>>10} KiB each)\n")

    pallas_on = bool(args.pallas)
    if pallas_on and ((batch + 31) // 32) % 128 != 0:
        raise SystemExit(
            f"--pallas: batch {batch} gives W={(batch+31)//32} words, "
            "not 128-lane aligned; the pallas kernel cannot engage")
    # under --pallas a kernel Mosaic refuses is the run's result, not
    # a reason to time the XLA gathers under another name
    digest = make_bfs_digest_batched(
        badj, core, DEPTH, batch, SEEDS, use_pallas=pallas_on)
    t0 = time.time()
    sums0, col0 = digest(slot_mats[0])
    sums0_np = np.asarray(sums0)
    sys.stderr.write(f"compile+first batch {time.time()-t0:.1f}s"
                     f"{' [pallas]' if pallas_on else ''}; "
                     f"level sums {sums0_np.tolist()}\n")

    # parity: per-query final-level counts of queries 0..31, computed
    # on device from the shipped first-word column via the batched
    # counts kernel, vs the CPU baseline's answers
    n_par = min(32, len(base_counts))
    par_counts = np.asarray(make_frontier_counts_batched(32)(col0))
    for i in range(n_par):
        if int(par_counts[i]) != base_counts[i]:
            sys.stderr.write(f"WARNING: query {i} device count "
                             f"{int(par_counts[i])} != cpu "
                             f"{base_counts[i]}\n")

    # sustained throughput: dispatch `pipe` distinct batches
    # back-to-back and sync once on their checksums
    times = []
    for r in range(runs):
        mats = slot_mats[1 + r * pipe: 1 + (r + 1) * pipe]
        t = time.perf_counter()
        handles = [digest(mm)[0] for mm in mats]
        for h in handles:
            np.asarray(h)
        times.append(time.perf_counter() - t)
    batch_ms = float(np.median(times)) * 1e3 / pipe
    qps = batch / batch_ms * 1e3
    sys.stderr.write(f"device sustained p50 {batch_ms:.1f} ms/batch "
                     f"({pipe} in flight) for {batch} queries = "
                     f"{qps:.0f} QPS\n")

    suffix = "_pallas" if pallas_on else ""
    print(json.dumps({
        "metric": f"bfs{DEPTH}_batched_qps_{n_edges//1_000_000}Medges"
                  f"{suffix}",
        "value": round(qps, 1),
        "unit": "qps",
        "vs_baseline": round(qps / base_qps, 3),
    }))


if __name__ == "__main__":
    main()
