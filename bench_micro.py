"""Microbench: UID-set intersect bandwidth (BASELINE.json's second
metric, "UID-intersect GB/s").

Mirrors the reference's harness shape (algo/uidlist_test.go:290
BenchmarkListIntersect*: two sorted lists, size ratio + overlap sweep)
on the device kernels (ops/uidvec.intersect — vectorized searchsorted
membership). The CPU baseline is np.intersect1d on the same data.

The driver-facing benchmark stays bench.py (one JSON line); this is
the supplementary micro harness. Prints one JSON line per config and a
summary line.
"""

import json
import os
import sys
import time

import numpy as np

RUNS = 9


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


def setops_compressed_bench(runs: int = 5) -> dict:
    """`--setops-compressed`: compressed-vs-dense set algebra sweep
    (ops/codec.CompressedPack + ops/setops pack kernels).

    Axes: block-form mix (array/packed, bitmap, run) x three densities
    x selectivity (how many posting blocks actually overlap). For each
    config three arms are timed:

      dense       intersect_many over the already-dense uid vectors
                  (the old tier's steady state: dense CSR resident)
      decode+i    densify every pack, then intersect_many — what a
                  compressed-at-rest store WITHOUT compressed set
                  algebra would pay per query
      compressed  intersect_packs: descriptor skipping + bitmap word
                  AND + mixed-form probes, decoding survivors only

    The GATE (tools/check.sh): on the selective-intersection config,
    `compressed` must beat `decode+i` — block skipping is the whole
    point; losing it means the kernels regressed into decode-always.
    Also prints the resident-bytes ratio per mix (the >= 3x at-rest
    claim's microscale witness) and a compressed-vs-dense crossover
    table. Budget override: DGRAPH_TPU_SETOPS_BUDGET (ratio,
    default 1.0 = must simply win)."""
    from dgraph_tpu.ops import codec, setops

    budget = float(os.environ.get("DGRAPH_TPU_SETOPS_BUDGET", "1.0"))
    rng = np.random.default_rng(20260803)
    scratch = codec.DecodeScratch()

    def mk(mix: str, n: int, span: int, base: int = 0):
        if mix == "run":
            starts = np.unique(rng.integers(
                0, span, max(n // 64, 1), dtype=np.uint64))
            s = np.unique(np.concatenate(
                [np.arange(st, st + 64, dtype=np.uint64)
                 for st in starts]))[:n]
        elif mix == "bitmap":
            # dense inside few blocks
            s = np.unique(rng.integers(
                0, max(n * 3 // 2, 1), n, dtype=np.uint64))
        else:  # array/packed: sparse over the whole span
            s = np.unique(rng.integers(0, span, n, dtype=np.uint64))
        return s + np.uint64(base)

    def timed(fn):
        best = float("inf")
        got = None
        for _ in range(runs):
            t0 = time.perf_counter()
            got = fn()
            best = min(best, time.perf_counter() - t0)
        return best, got

    out = []
    # (mix, n per set, uid span) — three densities per form family
    configs = [
        ("array", 20_000, 1 << 34),   # sparse: packed blocks
        ("array", 200_000, 1 << 26),  # mid density
        ("bitmap", 200_000, 1 << 19),  # dense: bitmap blocks
        ("run", 100_000, 1 << 24),    # runny
    ]
    for mix, n, span in configs:
        shared = mk(mix, n // 4, span)
        sets = [np.unique(np.concatenate([mk(mix, n, span), shared]))
                for _ in range(4)]
        packs = [codec.compress(s) for s in sets]
        d_t, want = timed(lambda: setops.intersect_many(sets))
        dd_t, got_d = timed(lambda: setops.intersect_many(
            [p.densify() for p in packs]))
        c_t, got = timed(lambda: setops.intersect_packs(
            packs, scratch=scratch))
        assert np.array_equal(want, got) \
            and np.array_equal(want, got_d), mix
        u_t, uw = timed(lambda: setops.union_many(sets))
        cu_t, ug = timed(lambda: setops.union_packs(
            packs, scratch=scratch))
        assert np.array_equal(uw, ug), mix
        dense_b = sum(s.nbytes for s in sets)
        comp_b = sum(p.nbytes for p in packs)
        rec = {"metric": "setops_compressed", "mix": mix,
               "set_size": n, "span_bits": span.bit_length() - 1,
               "dense_intersect_ms": round(d_t * 1e3, 3),
               "decode_then_intersect_ms": round(dd_t * 1e3, 3),
               "compressed_intersect_ms": round(c_t * 1e3, 3),
               "dense_union_ms": round(u_t * 1e3, 3),
               "compressed_union_ms": round(cu_t * 1e3, 3),
               "bytes_dense": dense_b, "bytes_compressed": comp_b,
               "bytes_ratio": round(dense_b / max(comp_b, 1), 2),
               "vs_dense": round(d_t / max(c_t, 1e-9), 2),
               "vs_decode": round(dd_t / max(c_t, 1e-9), 2)}
        out.append(rec)
        print(json.dumps(rec))

    # the GATE config: selective intersection — a small probe set
    # against a huge posting list, almost no block overlap (the
    # reference's IntersectWith lin/bin regime; block skipping must
    # beat decoding the 2M-uid list)
    big = mk("array", 2_000_000, 1 << 36)
    probe = np.unique(np.concatenate(
        [mk("array", 2_000, 1 << 36), big[:: len(big) // 500]]))
    bigp, probep = codec.compress(big), codec.compress(probe)
    want = setops.intersect_many([probe, big])
    dd_t, _ = timed(lambda: setops.intersect_many(
        [probep.densify(), bigp.densify()]))
    c_t, got = timed(lambda: setops.intersect_packs(
        [probep, bigp], scratch=scratch))
    assert np.array_equal(want, got)
    ratio = dd_t / max(c_t, 1e-9)
    gate = {"metric": "setops_compressed_selective",
            "probe": len(probe), "list": len(big),
            "decode_then_intersect_ms": round(dd_t * 1e3, 3),
            "compressed_intersect_ms": round(c_t * 1e3, 3),
            "block_skip_speedup": round(ratio, 2),
            "budget_ratio": budget,
            "within_budget": ratio > budget}
    print(json.dumps(gate))
    return gate


def lint_timing_bench(runs: int = 3):
    """`--lint-timing`: dglint wall time, BOTH modes. Full tree
    (parse + per-file rules + the whole-program call-graph rules,
    dgraph_tpu/ + tests/) must stay < 5 s so the gate stays viable as
    a pre-commit / tier-1 CI hook; a warm `--changed-only` pass
    (summaries served from the content-hash manifest, whole-program
    rules still over every file) must stay < 1 s so `tools/check.sh`
    re-lints per save, not per coffee. One JSON line, microbench
    shape; non-zero exit when either budget is blown."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import tempfile

    from tools.dglint.core import (
        build_project, lint_incremental, lint_project,
    )

    root = os.path.dirname(os.path.abspath(__file__))
    times = []
    n_files = n_findings = 0
    for _ in range(runs):
        t0 = time.monotonic()
        proj = build_project(["dgraph_tpu", "tests"], root)
        findings = lint_project(proj)
        times.append(time.monotonic() - t0)
        n_files, n_findings = len(proj.files), len(findings)
    med = float(np.median(times))

    # incremental: seed a scratch manifest (cold, uncounted), then
    # measure warm passes — the per-save developer loop
    cache = os.path.join(tempfile.mkdtemp(prefix="dglint_bench_"),
                         "cache.json")
    lint_incremental(["dgraph_tpu", "tests"], root, cache)
    inc_times = []
    inc_findings = 0
    for _ in range(runs):
        t0 = time.monotonic()
        inc, _proj, stats = lint_incremental(
            ["dgraph_tpu", "tests"], root, cache)
        inc_times.append(time.monotonic() - t0)
        inc_findings = len(inc)
        assert stats["changed"] == 0, stats  # warm = fully cached
    inc_med = float(np.median(inc_times))

    full_budget = float(os.environ.get("DGRAPH_TPU_LINT_BUDGET",
                                       "5.0"))
    inc_budget = float(os.environ.get("DGRAPH_TPU_LINT_INC_BUDGET",
                                      "1.0"))
    rec = {
        "metric": "dglint_full_tree_s", "value": round(med, 3),
        "unit": "s", "best_s": round(min(times), 3),
        "incremental_s": round(inc_med, 3),
        "incremental_best_s": round(min(inc_times), 3),
        "files": n_files, "findings": n_findings,
        "budget_s": full_budget, "incremental_budget_s": inc_budget,
        "within_budget": med < full_budget and inc_med < inc_budget}
    assert inc_findings == n_findings, \
        (inc_findings, n_findings)  # cached verdicts match the full
    print(json.dumps(rec))
    return rec


def span_overhead_bench(n: int = 20_000, runs: int = 5,
                        budget_us: float = 5.0) -> dict:
    """`--span-overhead`: per-span cost of utils/tracing with
    recording ON vs OFF. The budget is < 5 µs/span — spans sit on the
    executor's per-stage paths, so regressions here show up as a perf
    cliff before any flamegraph would find them. One JSON line in the
    microbench shape; tests/test_tracing.py enforces the budget with
    generous CI slack (shared 1-core runners jitter)."""
    from dgraph_tpu.utils import tracing

    def per_span_us(enabled: bool) -> float:
        tracing.set_enabled(enabled)
        best = float("inf")
        try:
            for _ in range(runs):
                tracing.clear()
                t0 = time.perf_counter_ns()
                for _ in range(n):
                    with tracing.span("bench.span"):
                        pass
                best = min(best,
                           (time.perf_counter_ns() - t0) / n / 1e3)
        finally:
            tracing.set_enabled(True)
        return best

    off = per_span_us(False)
    on = per_span_us(True)
    tracing.clear()
    rec = {"metric": "span_overhead_us",
           "on_us": round(on, 3), "off_us": round(off, 3),
           "recording_cost_us": round(on - off, 3),
           "budget_us": budget_us, "within_budget": on < budget_us}
    print(json.dumps(rec))
    return rec


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

    from bench import init_backend

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


def _summary_mix():
    """The golden summary-shape queries + the warm GraphDB — ONE
    definition of the 'high-QPS mix' every decomposed overhead gate
    (stats, netfault) times, so the gates can never drift onto
    different mixes."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tests"))
    from golden import runner

    db = runner.get_db()
    qdir = os.path.join(os.path.dirname(runner.__file__), "queries")
    # the summary shapes: index roots, pagination/sort, counts, term
    # search — the high-QPS mix, not the analytical tail
    names = [n for n in runner.query_names()
             if any(k in n for k in (
                 "eq_root", "allofterms", "anyofterms", "pagination",
                 "count_at_root", "has_edge", "multi_sort"))]
    queries = []
    for n in names:
        with open(os.path.join(qdir, n + ".gql")) as f:
            queries.append(f.read())
    return db, queries


def _mix_pass_us(db, queries) -> float:
    """One timed pass over the summary mix, in µs."""
    t0 = time.perf_counter_ns()
    for q in queries:
        db.query_json(q)
    return (time.perf_counter_ns() - t0) / 1e3


def stats_overhead_bench(runs: int = 5,
                         budget_frac: float = None) -> dict:
    """`--stats-overhead`: cost of the ALWAYS-ON statistics plane (the
    observed-cost span observer, utils/coststore) on the golden
    summary workload — the 21M-regime query shapes at gate scale.

    Methodology: a differential A/B at a sub-1% effect size cannot
    resolve through 1-core CI scheduler noise (±5-10% run to run), so
    the gate decomposes instead: (1) measure the observer's
    per-observation cost on a synthetic stage record, best-of-N
    (deterministic to ~nanoseconds); (2) count the REAL observations
    one workload pass generates; (3) time the pass, best-of-N. The
    overhead fraction = observations x per-obs cost / pass time. The
    budget is < 1% (override with DGRAPH_TPU_STATS_BUDGET);
    tools/check.sh gates on the exit code."""
    if budget_frac is None:
        budget_frac = float(os.environ.get(
            "DGRAPH_TPU_STATS_BUDGET", "0.01"))
    from dgraph_tpu.utils import coststore

    db, queries = _summary_mix()

    def one_pass() -> float:
        return _mix_pass_us(db, queries)

    # (1) per-observation cost of the observer, synthetic stage record
    store = coststore.store()
    rec_stage = {"name": "eq", "dur_us": 42.0, "trace_id": "bench",
                 "args": {"pred": "name", "n": 1000}}
    n_syn = 20_000
    per_obs_us = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter_ns()
        for _ in range(n_syn):
            store.observe_span(rec_stage)
        per_obs_us = min(per_obs_us,
                         (time.perf_counter_ns() - t0) / n_syn / 1e3)
    # (2) + (3) real observation volume and pass time
    for _ in range(2):
        one_pass()  # warm plans, column caches, stats caches
    before = coststore.stats()["observations"]
    pass_us = one_pass()
    obs_per_pass = coststore.stats()["observations"] - before
    for _ in range(runs - 1):
        pass_us = min(pass_us, one_pass())
    coststore.reset()
    frac = obs_per_pass * per_obs_us / pass_us if pass_us else 0.0
    rec = {"metric": "stats_overhead",
           "queries": len(queries),
           "pass_ms": round(pass_us / 1e3, 3),
           "observations_per_pass": int(obs_per_pass),
           "per_observation_us": round(per_obs_us, 4),
           "overhead_frac": round(frac, 5),
           "budget_frac": budget_frac,
           "within_budget": frac < budget_frac}
    print(json.dumps(rec))
    return rec


def planner_overhead_bench(runs: int = 5,
                           budget_frac: float = None) -> dict:
    """`--planner-overhead`: cost of the adaptive planner's per-stage
    tier decisions on the golden summary workload, decomposed like the
    stats/pprof/netfault gates (a sub-1% A/B cannot resolve through
    shared-runner scheduler noise):

      (1) per-CONSULT cost (a choose() that hits the plan's decision
          cache — the rebuild/cold path) and per-SERVE cost (the
          executor's warm _routed plan-layer probe — the steady
          state), each best-of-N on a real compiled plan;
      (2) consults AND warm serves per pass, counted by the planner
          on the real workload (warm passes consult zero times; the
          serves term is what keeps this gate meaningful then);
      (3) pass time, best-of-N.

    overhead fraction = (consults x per-consult + serves x per-serve)
    / pass time, budget < 1% (DGRAPH_TPU_PLANNER_BUDGET overrides).

    Doubles as the PLANNER SMOKE: after warm-up the workload must
    reach a pass that BUILDS zero new decisions — every stage served
    its tier from the plan cache (re-optimization may fire while
    estimates settle, so convergence is the assertion, not
    first-pass silence). Non-zero exit on either failure."""
    if budget_frac is None:
        budget_frac = float(os.environ.get(
            "DGRAPH_TPU_PLANNER_BUDGET", "0.01"))
    db, queries = _summary_mix()
    pl = getattr(db, "planner_impl", None)
    assert pl is not None, \
        "summary-mix engine must run the adaptive planner"

    # (1) per-consult (choose with a cached decision) and per-serve
    # (the executor's warm _routed probe, incl. the per-request memo
    # reset a fresh request implies) on a real compiled plan
    from dgraph_tpu.query.executor import Executor

    parsed, plan = db.plan_cache.lookup(
        db, '{ q(func: eq(name, "Movie 1")) { uid name } }', None)
    est = {"estRows": 64, "estRowsMax": 1024, "basis": "stats"}
    avail = ("postings", "columnar", "compressed")
    pl.choose(plan, "eq", "name", est, avail)  # build outside timing
    n_syn = 20_000
    per_consult_us = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter_ns()
        for _ in range(n_syn):
            pl.choose(plan, "eq", "name", est, avail)
        per_consult_us = min(per_consult_us,
                             (time.perf_counter_ns() - t0) / n_syn
                             / 1e3)
    ex = Executor(db, db.coordinator.max_assigned(), plan=plan)
    builder = (lambda: pl.choose(plan, "eq", "name", est, avail))
    ex._routed(("eq", "name", 1), builder)  # seed the routing layer
    per_serve_us = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter_ns()
        for _ in range(n_syn):
            ex._dec_memo.clear()  # a fresh request's plan-layer hit
            ex._routed(("eq", "name", 1), builder)
        per_serve_us = min(per_serve_us,
                           (time.perf_counter_ns() - t0) / n_syn
                           / 1e3)

    # (2)+(3) real consult volume, pass time, and the convergence
    # smoke: a pass that serves every decision from the plan cache
    def one_pass() -> float:
        return _mix_pass_us(db, queries)

    for _ in range(2):
        one_pass()  # warm plans, column caches, cost cells
    converged_pass = None
    builds_last = -1
    for i in range(10):
        before = pl.stats()
        one_pass()
        after = pl.stats()
        builds_last = after["decisions"] - before["decisions"]
        if builds_last == 0:
            converged_pass = i + 3  # incl. the 2 warm passes
            break
    before = pl.stats()
    pass_us = one_pass()
    after = pl.stats()
    consults = after["consults"] - before["consults"]
    serves = after["warmServes"] - before["warmServes"]
    for _ in range(runs - 1):
        pass_us = min(pass_us, one_pass())
    frac = (consults * per_consult_us + serves * per_serve_us) \
        / pass_us if pass_us else 0.0
    rec = {"metric": "planner_overhead",
           "queries": len(queries),
           "pass_ms": round(pass_us / 1e3, 3),
           "consults_per_pass": int(consults),
           "warm_serves_per_pass": int(serves),
           "per_consult_us": round(per_consult_us, 4),
           "per_serve_us": round(per_serve_us, 4),
           "overhead_frac": round(frac, 5),
           "budget_frac": budget_frac,
           "cache_converged_after_pass": converged_pass,
           "builds_in_last_checked_pass": builds_last,
           "within_budget": frac < budget_frac
           and converged_pass is not None}
    print(json.dumps(rec))
    return rec


def pprof_overhead_bench(runs: int = 5, threads: int = 12,
                         stack_depth: int = 24,
                         budget_frac: float = None) -> dict:
    """`--pprof-overhead`: cost of the on-demand sampling profiler
    (utils/pprof) at its default rate, against the ISSUE's < 2%
    throughput-impact budget.

    Methodology mirrors --stats-overhead: a differential A/B at a
    ~1% effect size cannot resolve through shared-runner scheduler
    noise, so the gate decomposes. Each sample holds the GIL for one
    sys._current_frames() walk over every live thread — the HELD-GIL
    walk is the throughput theft (nothing else runs meanwhile), so
    overhead fraction = DEFAULT_HZ x per-sample walk time.

    Recalibrated (was: 12 GIL-spinning busy threads): the old
    population made the tight measurement loop pay a GIL-ACQUISITION
    wait per iteration — up to a switch interval behind each spinning
    thread — and that wait is not theft (a worker thread runs during
    it; in production the 100 Hz sampler pays it while the server
    makes progress). On a contended box the wait dominated the walk
    ~8x and the gate failed at 2.4% while the actual steal was well
    under budget. The population is now `threads` ALIVE, DEEP-STACKED
    but BLOCKED threads (realistic frames to walk, zero GIL
    contention), so the loop times exactly the held-GIL walk the
    decomposition multiplies by DEFAULT_HZ. Budget override:
    DGRAPH_TPU_PPROF_BUDGET."""
    import threading

    from dgraph_tpu.utils import pprof

    if budget_frac is None:
        budget_frac = float(os.environ.get(
            "DGRAPH_TPU_PPROF_BUDGET", "0.02"))
    stop = threading.Event()
    ready = []
    ready_lock = threading.Lock()

    def parked(depth: int):
        # build a realistic stack for the walk, then block GIL-free
        if depth:
            parked(depth - 1)
            return
        with ready_lock:
            ready.append(1)
        stop.wait()

    pool = [threading.Thread(target=parked, args=(stack_depth,),
                             daemon=True)
            for _ in range(threads)]
    for t in pool:
        t.start()
    end = time.monotonic() + 10
    while time.monotonic() < end:
        with ready_lock:
            if len(ready) == threads:
                break
        time.sleep(0.005)
    try:
        me = frozenset({threading.get_ident()})
        names = {t.ident: t.name for t in threading.enumerate()
                 if t.ident is not None}
        n = 2000
        per_sample_s = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter_ns()
            for _ in range(n):
                pprof.sample_once(me, names)
            per_sample_s = min(
                per_sample_s, (time.perf_counter_ns() - t0) / n / 1e9)
    finally:
        stop.set()
        for t in pool:
            t.join(timeout=2)
    frac = pprof.DEFAULT_HZ * per_sample_s
    rec = {"metric": "pprof_overhead",
           "hz": pprof.DEFAULT_HZ,
           "threads_sampled": threads,
           "stack_depth": stack_depth,
           "per_sample_us": round(per_sample_s * 1e6, 2),
           "overhead_frac": round(frac, 5),
           "budget_frac": budget_frac,
           "within_budget": frac < budget_frac}
    print(json.dumps(rec))
    return rec


def netfault_overhead_bench(runs: int = 5,
                            checks_per_op: int = 8,
                            budget_frac: float = None) -> dict:
    """`--netfault-overhead`: cost of the INERT network-fault seam
    (utils/netfault.py `armed()` — one falsy-dict check) on the wire
    hot paths, against the < 1% acceptance budget.

    Decomposed like the stats/pprof gates (a sub-1% A/B cannot
    resolve through scheduler noise): (1) the per-check cost of the
    disarmed seam, best-of-N over a tight loop; (2) a conservative
    nominal check count per served operation — one client _rpc_once
    plus the raft append+heartbeat sends a replicated write fans out
    (transport.send per peer), rounded UP to `checks_per_op`; (3) the
    per-query time of the golden summary mix (the same pass the stats
    gate times — the FASTEST ops the cluster serves, so the fraction
    is an upper bound: cluster ops also pay real network time these
    single-node queries don't). Budget override:
    DGRAPH_TPU_NETFAULT_BUDGET."""
    from dgraph_tpu.utils import netfault

    if budget_frac is None:
        budget_frac = float(os.environ.get(
            "DGRAPH_TPU_NETFAULT_BUDGET", "0.01"))
    assert not netfault.armed(), "gate must measure the INERT path"
    # (1) per-check cost, disarmed
    n_syn = 200_000
    per_check_us = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter_ns()
        for _ in range(n_syn):
            netfault.armed()
        per_check_us = min(per_check_us,
                           (time.perf_counter_ns() - t0) / n_syn / 1e3)
    # (3) per-query time on the summary mix (shared definition)
    db, queries = _summary_mix()
    for _ in range(2):
        _mix_pass_us(db, queries)  # warm plans and caches
    pass_us = min(_mix_pass_us(db, queries) for _ in range(runs))
    per_query_us = pass_us / max(1, len(queries))
    frac = checks_per_op * per_check_us / per_query_us
    rec = {"metric": "netfault_overhead",
           "queries": len(queries),
           "per_check_us": round(per_check_us, 5),
           "checks_per_op": checks_per_op,
           "per_query_us": round(per_query_us, 2),
           "overhead_frac": round(frac, 6),
           "budget_frac": budget_frac,
           "within_budget": frac < budget_frac}
    print(json.dumps(rec))
    return rec


def racecheck_overhead_bench(runs: int = 5,
                             accesses_per_op: int = 32,
                             budget_frac: float = None) -> dict:
    """`--racecheck-overhead`: cost of the ARMED attribute-access race
    witness (utils/racecheck) on the query hot path, against the < 5%
    acceptance budget the marked tier-1 concurrency suites run under.

    Decomposed like the stats/netfault gates (an A/B at this effect
    size cannot resolve through scheduler noise): (1) the per-sampled-
    access cost — armed minus unarmed tight loop over a registered
    probe class, best-of-N; (2) a conservative nominal sampled-access
    count per served operation — a MicroBatcher leader touches a few
    dozen witnessed attributes per query_json, rounded UP to
    `accesses_per_op` and max'd with the REAL sample count an armed
    batcher pass records; (3) the per-query time of the golden summary
    mix (the fastest ops served, so the fraction is an upper bound).
    Budget override: DGRAPH_TPU_RACECHECK_BUDGET."""
    from dgraph_tpu.engine.batcher import MicroBatcher
    from dgraph_tpu.utils import racecheck

    if budget_frac is None:
        budget_frac = float(os.environ.get(
            "DGRAPH_TPU_RACECHECK_BUDGET", "0.05"))

    class _Probe:
        def __init__(self):
            self.x = 0

    def spin(p, n):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            p.x = p.x + 1  # one witnessed read + one witnessed write
        return (time.perf_counter_ns() - t0) / n / 1e3

    # (1) per-sampled-access delta: unarmed baseline vs armed probe
    n_syn = 50_000
    base_us = min(spin(_Probe(), n_syn) for _ in range(runs))
    racecheck.register(_Probe)
    racecheck.enable()
    try:
        armed_us = min(spin(_Probe(), n_syn) for _ in range(runs))
    finally:
        racecheck.disable()
    per_access_us = max(0.0, (armed_us - base_us) / 2)

    # (3) per-query time, unarmed (shared golden-mix definition)
    db, queries = _summary_mix()
    for _ in range(2):
        _mix_pass_us(db, queries)  # warm plans and caches
    pass_us = min(_mix_pass_us(db, queries) for _ in range(runs))
    per_query_us = pass_us / max(1, len(queries))

    # (2) real sampled-access volume of an armed batcher pass
    racecheck.enable()
    try:
        batcher = MicroBatcher(db, window_us=0)
        for q in queries:
            batcher.query_json(q)
        measured = racecheck.stats()["samples"] / max(1, len(queries))
    finally:
        racecheck.disable()
    per_op = max(accesses_per_op, int(measured) + 1)

    frac = per_op * per_access_us / per_query_us
    rec = {"metric": "racecheck_overhead",
           "queries": len(queries),
           "per_access_us": round(per_access_us, 5),
           "accesses_per_op": per_op,
           "measured_samples_per_op": round(measured, 2),
           "per_query_us": round(per_query_us, 2),
           "overhead_frac": round(frac, 6),
           "budget_frac": budget_frac,
           "within_budget": frac < budget_frac}
    print(json.dumps(rec))
    return rec


def watchdog_overhead_bench(runs: int = 5,
                            budget_frac: float = None) -> dict:
    """`--watchdog-overhead`: cost of the always-on alerting plane
    (utils/watchdog's evaluator tick + the per-request reqlog observer
    utils/alerts feeds its SLO windows with) against the < 1%
    acceptance budget.

    Decomposed like the stats/netfault gates (a sub-1% A/B cannot
    resolve through scheduler noise): (1) the per-tick cost of
    Watchdog.tick() on a WARM manager — every default rule loaded,
    SLO windows populated with op+tenant series, signal providers
    registered, healthy signal values so no rule fires — best-of-N;
    the evaluator runs once per tick_s, so its duty cycle is
    per_tick / tick_s; (2) the per-request cost of
    AlertManager.observe_request on a realistic reqlog record,
    best-of-N; (3) the per-query time of the golden summary mix (the
    fastest ops served, so the observer fraction is an upper bound).
    overhead = per_tick/(tick_s) + per_obs/per_query. Budget
    override: DGRAPH_TPU_WATCHDOG_BUDGET."""
    from dgraph_tpu.utils import alerts, watchdog

    if budget_frac is None:
        budget_frac = float(os.environ.get(
            "DGRAPH_TPU_WATCHDOG_BUDGET", "0.01"))
    tick_s = 1.0
    wd = watchdog.Watchdog(tick_s=tick_s,
                           manager=alerts.AlertManager())
    wd.register_signals("bench", lambda: {
        "raft_apply_lag": 3.0, "raft_peer_silent_s": 0.2,
        "cdc_max_lag": 1.0})
    rec_ok = {"op": "query", "outcome": "ok", "tenant": "t0"}
    for _ in range(2_000):
        wd.manager.observe_request(rec_ok)
    wd.tick()  # baseline tick: rate rules need a prev snapshot

    # (1) per-tick cost, warm manager, nothing firing
    n_ticks = 2_000
    per_tick_us = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter_ns()
        for _ in range(n_ticks):
            wd.tick()
        per_tick_us = min(
            per_tick_us, (time.perf_counter_ns() - t0) / n_ticks / 1e3)

    # (2) per-observation cost of the reqlog observer
    n_syn = 50_000
    per_obs_us = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter_ns()
        for _ in range(n_syn):
            wd.manager.observe_request(rec_ok)
        per_obs_us = min(
            per_obs_us, (time.perf_counter_ns() - t0) / n_syn / 1e3)

    # (3) per-query time on the summary mix (shared definition)
    db, queries = _summary_mix()
    for _ in range(2):
        _mix_pass_us(db, queries)  # warm plans and caches
    pass_us = min(_mix_pass_us(db, queries) for _ in range(runs))
    per_query_us = pass_us / max(1, len(queries))

    tick_frac = per_tick_us / (tick_s * 1e6)
    obs_frac = per_obs_us / per_query_us
    frac = tick_frac + obs_frac
    rec = {"metric": "watchdog_overhead",
           "queries": len(queries),
           "per_tick_us": round(per_tick_us, 3),
           "tick_s": tick_s,
           "tick_frac": round(tick_frac, 6),
           "per_observation_us": round(per_obs_us, 5),
           "per_query_us": round(per_query_us, 2),
           "observer_frac": round(obs_frac, 6),
           "overhead_frac": round(frac, 6),
           "budget_frac": budget_frac,
           "within_budget": frac < budget_frac}
    print(json.dumps(rec))
    return rec


def main():
    if "--lint-timing" in sys.argv:
        if not lint_timing_bench()["within_budget"]:
            sys.exit(1)
        return
    if "--span-overhead" in sys.argv:
        span_overhead_bench()
        return
    if "--lookup-crossover" in sys.argv:
        lookup_crossover_bench()
        return
    if "--stats-overhead" in sys.argv:
        if not stats_overhead_bench()["within_budget"]:
            sys.exit(1)
        return
    if "--planner-overhead" in sys.argv:
        if not planner_overhead_bench()["within_budget"]:
            sys.exit(1)
        return
    if "--pprof-overhead" in sys.argv:
        if not pprof_overhead_bench()["within_budget"]:
            sys.exit(1)
        return
    if "--netfault-overhead" in sys.argv:
        if not netfault_overhead_bench()["within_budget"]:
            sys.exit(1)
        return
    if "--racecheck-overhead" in sys.argv:
        if not racecheck_overhead_bench()["within_budget"]:
            sys.exit(1)
        return
    if "--watchdog-overhead" in sys.argv:
        if not watchdog_overhead_bench()["within_budget"]:
            sys.exit(1)
        return
    if "--setops-compressed" in sys.argv:
        if not setops_compressed_bench()["within_budget"]:
            sys.exit(1)
        return

    kway_bench()

    from bench import init_backend

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
