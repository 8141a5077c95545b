"""Microbench: UID-set intersect bandwidth (BASELINE.json's second
metric, "UID-intersect GB/s").

Mirrors the reference's harness shape (algo/uidlist_test.go:290
BenchmarkListIntersect*: two sorted lists, size ratio + overlap sweep)
on the device kernels (ops/uidvec.intersect — vectorized searchsorted
membership). The CPU baseline is np.intersect1d on the same data.

Two uses, both on a chip: the default run prints one JSON line per
config and the `uid_intersect_gbps` summary line that BASELINE.md's
second metric is compared with; `--lookup-crossover` prints the grid
`ops/uidvec.lookup_cosorts` was fitted on. Neither is the repo's
benchmark: that is benchmark/run.py, and its record PERF_LEDGER.jsonl.
"""

import argparse
import json
import os
import time

import numpy as np

RUNS = 9


def init_backend():
    """Place the compile cache, then take the devices. The platform is
    whatever JAX_PLATFORMS says, else the chip; no chip raises
    (utils/backend.NoAcceleratorError). Returns (devices, platform)."""
    from dgraph_tpu.utils.backend import (
        configure_compile_cache, require_devices,
    )

    configure_compile_cache()
    devs = require_devices()
    return devs, devs[0].platform


def make_pair(n_a: int, ratio: int, overlap: float, seed: int = 0):
    """Two sorted unique uint32 lists; |b| = n_a * ratio; ~overlap of
    a's elements also appear in b (the reference's sweep axes)."""
    rng = np.random.default_rng(seed)
    n_b = n_a * ratio
    space = np.uint32(4_000_000_000)
    b = np.unique(rng.integers(0, space, n_b, dtype=np.uint32))
    take = rng.random(len(b)) < (overlap * n_a / max(len(b), 1))
    shared = b[take][:n_a]
    fresh = np.unique(rng.integers(0, space, n_a, dtype=np.uint32))
    a = np.unique(np.concatenate([shared, fresh]))[:n_a]
    return a, b


def kway_bench():
    """k-way vs pairwise host set algebra (ops/setops): the executor's
    old fold was k-1 union1d accumulator re-sorts / a size-blind
    intersect fold; union_many is concat + ONE sort, intersect_many is
    smallest-first galloping. Sweeps k = 8 / 64 / 512 sets so the
    setops win is tracked independently of the query suite."""
    from functools import reduce

    from dgraph_tpu.ops import setops

    rng = np.random.default_rng(7)
    out = []
    for k, n in [(8, 65_536), (64, 8_192), (512, 1_024)]:
        space = 4 * k * n
        sets = [np.unique(rng.integers(0, space, n).astype(np.uint64))
                for _ in range(k)]
        # one shared run so intersections are non-empty
        shared = np.unique(
            rng.integers(0, space, n // 4).astype(np.uint64))
        isets = [np.unique(np.concatenate([s[: n // 2], shared]))
                 for s in sets]

        def timed(fn, runs=5):
            best = float("inf")
            for _ in range(runs):
                t = time.perf_counter()
                got = fn()
                best = min(best, time.perf_counter() - t)
            return best, got

        pu_t, pu = timed(lambda: reduce(np.union1d, sets))
        ku_t, ku = timed(lambda: setops.union_many(sets))
        assert np.array_equal(pu, ku)
        pi_t, pi = timed(lambda: reduce(
            lambda a, b: np.intersect1d(a, b, assume_unique=True),
            isets))
        ki_t, ki = timed(lambda: setops.intersect_many(isets))
        assert np.array_equal(pi, ki)
        rec = {"metric": "setops_kway", "sets": k, "set_size": n,
               "union_pairwise_ms": round(pu_t * 1e3, 2),
               "union_kway_ms": round(ku_t * 1e3, 2),
               "union_speedup": round(pu_t / max(ku_t, 1e-9), 2),
               "intersect_pairwise_ms": round(pi_t * 1e3, 2),
               "intersect_kway_ms": round(ki_t * 1e3, 2),
               "intersect_speedup": round(pi_t / max(ki_t, 1e-9), 2)}
        out.append(rec)
        print(json.dumps(rec))
    best = max(r["union_speedup"] for r in out)
    print(json.dumps({"metric": "setops_kway_union_speedup",
                      "value": best, "unit": "x"}))


def lookup_crossover_bench(
        n_qs=(1_024, 4_096, 16_384, 262_144),
        n_ts=(16_384, 524_288, 2_097_152), calls: int = 8) -> list:
    """`--lookup-crossover`: device time of the two lowerings
    `ops/uidvec.lookup_idx` picks between, over the grid its rule was
    fitted on (n_q sorted queries x n_t table rows): the scan lowering
    of jnp.searchsorted (cost ~ n_q * log2 n_t gathered elements)
    against sorted_lookup's co-sort (two lax.sorts, cost ~ n_q + n_t).
    Each is one jitted program with the table and the queries as its
    parameters, as the served programs have them, and its time is the
    device's own: the mean of the program's events in a profiler
    trace of `calls` back-to-back calls (a host clock around one small
    kernel measures the dispatch). Prints one JSON line per grid point
    with both times, the faster one, and what lookup_idx picks there
    on a sort backend. Meant for the chip; where the trace has no
    device plane (the CPU) it falls back to the host clock, and the
    line's `clock` says so."""
    import glob
    import tempfile

    _devs, platform = init_backend()
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from dgraph_tpu.ops import uidvec
    from dgraph_tpu.utils.tracing import profile_device

    def timed_ms(fn, *args):
        jax.block_until_ready(fn(*args))
        with tempfile.TemporaryDirectory(
                prefix="lookup_crossover_") as tmp:
            with profile_device(tmp):
                t = time.perf_counter()
                for _ in range(calls):
                    out = fn(*args)
                jax.block_until_ready(out)
                host_ms = (time.perf_counter() - t) * 1e3 / calls
            pd = ProfileData.from_file(glob.glob(os.path.join(
                tmp, "plugins", "profile", "*", "*.xplane.pb"))[0])
        ns = [e.duration_ns for plane in pd.planes
              if plane.name.startswith("/device:")
              for ln in plane.lines if ln.name == "XLA Modules"
              for e in ln.events]
        if len(ns) == calls:
            return sum(ns) / calls / 1e6, "device"
        return host_ms, "host"

    rng = np.random.default_rng(25)
    out = []
    for n_t in n_ts:
        table = np.unique(rng.integers(
            1, 1 << 31, 2 * n_t, dtype=np.uint32))[:n_t - 7]
        dt = jax.device_put(uidvec.from_numpy(table, size=n_t))
        for n_q in n_qs:
            q = np.unique(np.concatenate([
                rng.choice(table, min(n_q // 2, len(table)),
                           replace=False),
                rng.integers(1, 1 << 31, n_q, dtype=np.uint32)]))
            q = q[np.sort(rng.choice(len(q), n_q - 5, replace=False))]
            dq = jax.device_put(uidvec.from_numpy(q, size=n_q))
            want = np.searchsorted(np.asarray(dt), np.asarray(dq))
            ms = {}
            for name, lookup in (("scan", jnp.searchsorted),
                                 ("cosort", uidvec.sorted_lookup)):
                fn = jax.jit(lookup)
                assert np.array_equal(np.asarray(fn(dt, dq)), want), \
                    (name, n_q, n_t)
                ms[name], clock = timed_ms(fn, dt, dq)
            rec = {"metric": "lookup_crossover", "platform": platform,
                   "clock": clock, "n_q": n_q, "n_t": n_t,
                   "scan_ms": round(ms["scan"], 4),
                   "cosort_ms": round(ms["cosort"], 4),
                   "faster": min(ms, key=ms.get),
                   "rule_picks": "cosort"
                   if uidvec.lookup_cosorts(n_q, n_t) else "scan"}
            out.append(rec)
            print(json.dumps(rec), flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lookup-crossover", action="store_true",
                    help="time lookup_idx's two lowerings over the "
                         "grid its rule was fitted on, and stop")
    args = ap.parse_args(argv)
    if args.lookup_crossover:
        lookup_crossover_bench()
        return

    kway_bench()

    _devs, platform = init_backend()
    import jax
    import jax.numpy as jnp

    from dgraph_tpu.ops.uidvec import from_numpy, intersect, to_numpy

    results = []
    # K pairs per device call (vmap) — the engine's usage shape: one
    # batched call per query level, not one dispatch per pair (a lone
    # small kernel only measures the fixed dispatch cost)
    for n_a, ratio, overlap, k in [(1_000_000, 1, 0.3, 8),
                                   (65_536, 8, 0.1, 128),
                                   (16_384, 1, 0.3, 1024)]:
        pairs = [make_pair(n_a, ratio, overlap, seed=s)
                 for s in range(k)]
        sz_a = max(len(a) for a, _ in pairs)
        sz_b = max(len(b) for _, b in pairs)
        da = jax.device_put(jnp.stack(
            [from_numpy(a, size=1 << (sz_a - 1).bit_length())
             for a, _ in pairs]))
        db = jax.device_put(jnp.stack(
            [from_numpy(b, size=1 << (sz_b - 1).bit_length())
             for _, b in pairs]))

        t = time.perf_counter()
        want = [np.intersect1d(a, b, assume_unique=True)
                for a, b in pairs]
        cpu_s = time.perf_counter() - t

        fn = jax.jit(jax.vmap(intersect))
        out = np.asarray(fn(da, db))
        for i in range(k):
            assert np.array_equal(to_numpy(out[i]), want[i]), i
        # a 4-byte digest readback forces completion; the measured
        # empty-readback floor (one dispatch round trip) is subtracted
        # so only device time counts
        digest = jax.jit(
            lambda x, y: jnp.sum(jax.vmap(intersect)(x, y),
                                 dtype=jnp.uint32))
        floor_fn = jax.jit(lambda x: jnp.sum(x[:1, :8],
                                             dtype=jnp.uint32))
        np.asarray(digest(da, db))
        np.asarray(floor_fn(da))
        times, floors = [], []
        for _ in range(RUNS):
            t = time.perf_counter()
            np.asarray(floor_fn(da))
            floors.append(time.perf_counter() - t)
            t = time.perf_counter()
            np.asarray(digest(da, db))
            times.append(time.perf_counter() - t)
        dev_s = max(1e-6, float(np.median(times)) -
                    float(np.median(floors)))
        nbytes = (da.size + db.size) * 4
        rec = {"config": f"a={n_a} ratio={ratio} "
                         f"overlap={overlap} pairs={k}",
               "platform": platform,
               "device_gbps": round(nbytes / dev_s / 1e9, 2),
               "cpu_gbps": round(nbytes / cpu_s / 1e9, 2),
               "speedup": round(cpu_s / dev_s, 2)}
        results.append(rec)
        print(json.dumps(rec))
    best = max(r["device_gbps"] for r in results)
    print(json.dumps({"metric": "uid_intersect_gbps", "value": best,
                      "unit": "GB/s", "platform": platform}))


if __name__ == "__main__":
    main()
