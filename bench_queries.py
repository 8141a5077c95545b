"""End-to-end query-surface benchmark at the reference's 21M-RDF
acceptance regime (systest/21million/test-21million.sh).

bench.py measures the raw traversal kernel; THIS measures what a user
sees: full query strings through GraphDB — parse -> plan -> execute ->
JSON — over the deterministic movie graph scaled to ~21M RDF
(tests/golden/dataset.py, QBENCH_SCALE=800 by default; the golden
suite is the same graph at scale 1).

Workload: the golden conformance suite's queries (uid literals
remapped to the scaled uid bases) plus a depth-3 @recurse and a
weighted shortest-path — the reference's own acceptance queries'
families (systest/21million/queries/query-0??).

Two engines answer the identical workload:
  host    — prefer_device=False: the vectorized-NumPy executor path
  device  — prefer_device=True: the TPU tier serves expansions,
            range scans and order keys

Correctness at scale: both paths must produce byte-identical JSON for
every query (the committed goldens validate scale 1; at 21M the
host/device cross-check is the oracle). Any mismatch is reported and
fails the run.

Prints ONE BENCH-format JSON line:
  {"metric": "query_surface_p50_ms_<N>M", "value": <device p50 ms>,
   "unit": "ms", "vs_baseline": <host_p50 / device_p50>,
   ...detail fields...}
and writes per-query timings to BENCH_QUERIES.json.

Every device call pays a fixed dispatch cost; small index-hit queries
stay on the host path by design (device_min_edges and the executor's
measured-dispatch gate), so the tier only engages where batched device
work can win.

The platform is whatever JAX_PLATFORMS says, else the chip
(bench.init_backend); no chip, a parity mismatch or any exception
exits non-zero. QBENCH_SCALE is never shrunk behind the caller: a CPU
run that wants a small graph sets it.
"""

import json
import os
import sys
import time

SCALE = int(os.environ.get("QBENCH_SCALE", 800))
REPEATS = int(os.environ.get("QBENCH_REPEATS", 3))

# --concurrency mode: open-loop arrival counts
CONC_REQUESTS = int(os.environ.get("QBENCH_CONC_REQUESTS", 2000))
CONC_WINDOW_US = int(os.environ.get("QBENCH_BATCH_WINDOW_US", 500))

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "tests"))
from golden.workload import load_workload  # noqa: E402


def build_db(scale: int, prefer_device: bool):
    import tempfile

    from dgraph_tpu.engine.db import GraphDB
    from dgraph_tpu.ingest.bulk import bulk_load

    from golden.dataset import generate

    t0 = time.time()
    schema, quads = generate(scale)
    n = len(quads)
    sys.stderr.write(f"dataset: {n} RDF at scale {scale} "
                     f"({time.time()-t0:.0f}s)\n")
    t0 = time.time()
    with tempfile.NamedTemporaryFile("w", suffix=".rdf",
                                     delete=False) as f:
        path = f.name
        f.write("\n".join(quads))
    quads.clear()
    db = GraphDB(prefer_device=prefer_device)
    bulk_load([path], schema=schema, db=db)
    os.unlink(path)
    sys.stderr.write(f"bulk load: {n/(time.time()-t0):,.0f} RDF/s "
                     f"({time.time()-t0:.0f}s)\n")
    return db, n


def run_workload(db, workload, repeats: int) -> dict[str, list[float]]:
    times: dict[str, list[float]] = {name: [] for name, _ in workload}
    outputs: dict[str, str] = {}
    for r in range(repeats):
        for name, q in workload:
            t = time.perf_counter()
            got = db.query(q)
            dt = time.perf_counter() - t
            times[name].append(dt)
            if r == 0:
                outputs[name] = json.dumps(got["data"], sort_keys=True)
    times["__outputs__"] = outputs  # type: ignore[assignment]
    return times


def _measure_resident(db) -> dict:
    """Resident posting bytes under the compressed tier (the ISSUE's
    acceptance metric): per-tablet compressed token-index exports
    (tabstats compressedResidency) vs what the SAME indexes cost as
    dense CSR exports, plus the tile LRU's device/host accounting and
    high-water marks and the tabstats decoded total. `ratio` is the
    dense/compressed resident-posting-bytes factor the >= 3x gate
    reads."""
    from dgraph_tpu.storage.tablet import TokenIndexCSR
    from dgraph_tpu.storage.tabstats import (
        compressed_residency, tablet_stats,
    )

    at_rest = decoded = dense_csr = 0
    post_comp = post_dense = 0
    per_pred = {}
    ts = db.coordinator.max_assigned()
    for pred, tab in db.tablets.items():
        st = tablet_stats(tab)
        comp = compressed_residency(tab)["tokenPacks"]
        at_rest += st["bytesCompressed"]
        decoded += st["bytesDecoded"]
        if comp and tab.index:
            csr = TokenIndexCSR(tab.index)
            packs = tab.token_index_packs(ts)
            dense_csr += csr.nbytes
            post_dense += csr.posting_nbytes
            post_comp += packs.posting_nbytes
            per_pred[pred] = {
                "packs": comp, "dense_csr": csr.nbytes,
                "posting_packs": packs.posting_nbytes,
                "posting_dense": csr.posting_nbytes,
                "ratio": round(csr.posting_nbytes
                               / max(packs.posting_nbytes, 1), 2)}
    lru = db.device_cache.stats()
    scratch = db.decode_scratch.stats() \
        if getattr(db, "decode_scratch", None) else {}
    return {
        "bytes_at_rest": at_rest,
        "bytes_decoded": decoded,
        "dense_index_bytes": dense_csr,
        # posting (uid-plane) bytes: the >= 3x acceptance ratio —
        # the token-key map is excluded because BOTH tiers carry it
        # byte-identically (it is the probe map, not posting data)
        "posting_bytes_compressed": post_comp,
        "posting_bytes_dense": post_dense,
        "ratio": round(post_dense / max(post_comp, 1), 2),
        "export_ratio": round(dense_csr / max(at_rest, 1), 2),
        "tile_lru": {"device_bytes": lru["bytes"],
                     "host_bytes": lru["hostBytes"],
                     "peak_device_bytes": lru["peakBytes"],
                     "peak_host_bytes": lru["peakHostBytes"],
                     "evictions": lru["evictions"]},
        "decode_scratch": scratch,
        "per_pred": per_pred,
    }


def _measure_encode_100k(db, scale: int) -> dict:
    import numpy as np

    rows = min(100_000, 1200 * scale)
    q = ('{ q(func: has(rating), first: %d) '
         '{ uid name rating runtime } }' % rows)
    db.query(q)
    db.query_json(q)
    old_enc, old_dump, new_enc = [], [], []
    for _ in range(3):
        out = db.query(q)
        old_enc.append(
            out["extensions"]["latency"]["encoding_ns"] / 1e6)
        t0 = time.perf_counter()
        json.dumps(out["data"], separators=(",", ":"))
        old_dump.append((time.perf_counter() - t0) * 1e3)
        s = db.query_json(q)
        new_enc.append(json.loads(s)["extensions"]["latency"]
                       ["encoding_ns"] / 1e6)
    old_ms = float(np.median(old_enc) + np.median(old_dump))
    new_ms = float(np.median(new_enc))
    return {"rows": rows,
            "dict_dumps_ms": round(old_ms, 1),
            "columnar_ms": round(new_ms, 1),
            "speedup": round(old_ms / max(new_ms, 1e-9), 1)}


def _conc_workload(db, scale: int) -> tuple[list, list]:
    """(repeated-skeleton, mixed) workloads for --concurrency mode.

    repeated-skeleton = app-style parameterized families — point
    lookups, term search with a range filter, uid fetches — many
    literal bindings per skeleton, exactly what the plan cache keys
    on. mixed = a golden-suite slice (one-off structures)."""
    rep = []
    for i in range(48):
        rep.append('{ q(func: eq(name, "Movie %d")) '
                   '{ uid name initial_release_date } }' % (i * 7))
    for i in range(24):
        rep.append('{ q(func: eq(runtime, %d)) @filter(ge(rating, 2.0)) '
                   '{ uid runtime rating } }' % (60 + i))
    for i in range(24):
        rep.append('{ q(func: anyofterms(name, "movie %d")) '
                   '@filter(le(initial_release_date, "1999-01-01")) '
                   '{ uid name } }' % i)
    for i in range(16):
        rep.append('{ q(func: uid(%s)) { uid name rating runtime } }'
                   % hex(0x20000 * scale + i))
    mixed = [q for _, q in load_workload(scale)[:24]]
    return rep, mixed


# the open-loop arrival scheduler + percentile summarizers moved to
# the shared bench module (dgraph_tpu/bench/openloop.py) so this
# gate, tools/dgbench.py and the CI load smoke agree on what
# "offered load" and "p99" mean; the local names stay as aliases
# (BENCH_BATCH.json schema unchanged)
from dgraph_tpu.bench.openloop import (  # noqa: E402
    occupancy as _occupancy,
    percentiles as _pcts,
    run_open_loop as _run_open_loop,
)


def main_concurrency(concurrency: int) -> int:
    """--concurrency N: cold-compile vs warm-cache vs batched columns
    at the bench regime -> BENCH_BATCH.json.

    Sequential columns measure the serving path (query_json) with the
    plan cache off (interpreted) and on (warm); concurrent columns
    drive an open-loop arrival schedule through N workers with
    sequential dispatch (shared reader lock, no batcher) vs the
    micro-batcher. Parity: batched responses must be byte-identical
    (data payload) to unbatched ones."""
    from bench import init_backend
    from dgraph_tpu.engine.batcher import MicroBatcher
    from dgraph_tpu.query.plan import PlanCache
    from dgraph_tpu.utils import metrics
    from dgraph_tpu.utils.rwlock import RWLock

    devs, platform = init_backend()
    sys.stderr.write(f"jax devices: {devs} (platform={platform})\n")
    scale = SCALE
    db, n_rdf = build_db(scale, prefer_device=True)
    rep, mixed = _conc_workload(db, scale)

    def data_of(body: str) -> str:
        return json.dumps(json.loads(body)["data"], sort_keys=True)

    # -- sequential: interpreted vs cold-compile vs warm-cache --------
    def seq(qs, repeats=3):
        ts = []
        for _ in range(repeats):
            for q in qs:
                t = time.perf_counter()
                db.query_json(q)
                ts.append(time.perf_counter() - t)
        return ts

    db.plan_cache = None
    seq(rep, 1)  # warm tablets/tiles outside timing
    seq(mixed, 1)
    pc = PlanCache(256)
    db.plan_cache = pc  # empty: the first pass IS the cold run
    before = metrics.counters_snapshot()
    cold = seq(rep, 1)
    # interleave the interpreted and warm arms pass by pass so
    # box-level noise (CPU steal on shared hosts) hits both equally
    interp, warm, interp_mixed, warm_mixed = [], [], [], []
    for _ in range(4):
        db.plan_cache = None
        interp += seq(rep, 1)
        interp_mixed += seq(mixed, 1)
        db.plan_cache = pc
        warm += seq(rep, 1)
        warm_mixed += seq(mixed, 1)
    delta = metrics.counters_delta(before)
    hits = delta.get("plan_cache_hits", 0)
    misses = delta.get("plan_cache_misses", 0)

    # -- concurrent: sequential dispatch vs micro-batched -------------
    # offered load = QBENCH_CONC_LOAD (default 0.85) of MEASURED
    # concurrent capacity (closed-loop probe): threads on one GIL
    # lose real capacity to contention, so sizing off single-thread
    # latency would saturate the open loop and measure nothing but
    # queue growth
    import threading as _threading
    probe_reqs = (rep * 3)[:300]
    probe_next = [0]
    probe_lock = _threading.Lock()
    rw_probe = RWLock()

    def probe_worker():
        while True:
            with probe_lock:
                i = probe_next[0]
                if i >= len(probe_reqs):
                    return
                probe_next[0] += 1
            with rw_probe.read:
                db.query_json(probe_reqs[i])

    t0 = time.perf_counter()
    pthreads = [_threading.Thread(target=probe_worker)
                for _ in range(concurrency)]
    for t in pthreads:
        t.start()
    for t in pthreads:
        t.join()
    capacity = len(probe_reqs) / (time.perf_counter() - t0)
    rate = float(os.environ.get("QBENCH_CONC_LOAD", 0.85)) * capacity
    # production-shaped arrival process, deterministic so both columns
    # replay the identical stream: half the traffic arrives as
    # fan-out BURSTS — 8 copies of one hot query at the same instant
    # (dashboard fan-out / cache stampede, the canonical micro-batch
    # scenario and the ISSUE's "concurrent same-skeleton" workload) —
    # the other half as independent singles over the full repeated +
    # mixed families
    import random as _random
    rng = _random.Random(20260803)
    hot = rep[:8]
    reqs = []       # query per arrival
    burst_of = []   # arrival-slot index each request shares
    slot = 0
    while len(reqs) < CONC_REQUESTS:
        if rng.random() < 0.125:  # 1 burst in 8 slots = 50% of traffic
            q = hot[rng.randrange(len(hot))]
            for _ in range(min(8, CONC_REQUESTS - len(reqs))):
                reqs.append(q)
                burst_of.append(slot)
        else:
            r = rng.random()
            fam = rep if r < 0.7 else mixed
            reqs.append(fam[rng.randrange(len(fam))])
            burst_of.append(slot)
        slot += 1
    rw = RWLock()

    expected = {q: data_of(db.query_json(q)) for q in set(reqs)}

    def seq_submit(q):
        with rw.read:
            db.query_json(q)

    seq_lat = _run_open_loop(seq_submit, reqs, concurrency, rate,
                             burst_of)

    mb = MicroBatcher(db, window_us=CONC_WINDOW_US,
                      read_lock=lambda: rw.read)
    before = metrics.counters_snapshot()
    mismatch = [0]

    def batch_submit(q):
        out = mb.query_json(q)
        if data_of(out) != expected[q]:
            mismatch[0] += 1

    bat_lat = _run_open_loop(batch_submit, reqs, concurrency, rate,
                             burst_of)
    bdelta = metrics.counters_delta(before)
    dispatches = bdelta.get("batch_dispatches", 0)

    out = {
        "summary": {
            "metric": f"query_batched_p99_ms_{n_rdf//1_000_000}M",
            "value": _pcts(bat_lat)["p99_ms"],
            "unit": "ms",
            "vs_baseline": round(
                _pcts(seq_lat)["p99_ms"]
                / max(_pcts(bat_lat)["p99_ms"], 1e-9), 3),
            "concurrency": concurrency,
            "requests": CONC_REQUESTS,
            "offered_qps": round(rate, 1),
            "batch_window_us": CONC_WINDOW_US,
            "parity_ok": mismatch[0] == 0,
            "platform": platform,
            "scale": scale,
            "rdf": n_rdf,
        },
        "columns": {
            "interpreted_seq": {**_pcts(interp), "workload": "repeated"},
            "interpreted_seq_mixed": {**_pcts(interp_mixed),
                                      "workload": "mixed"},
            "cold_compile": {**_pcts(cold),
                             "note": "first run per skeleton: parse + "
                                     "plan compile + jit warm"},
            "warm_cache": {**_pcts(warm), "workload": "repeated",
                           "hit_rate": round(
                               hits / max(hits + misses, 1), 4)},
            "warm_cache_mixed": {**_pcts(warm_mixed),
                                 "workload": "mixed"},
            "sequential_dispatch": {**_pcts(seq_lat),
                                    "concurrency": concurrency},
            "batched": {**_pcts(bat_lat), "concurrency": concurrency,
                        "dispatches": dispatches,
                        "mean_occupancy": _occupancy(CONC_REQUESTS,
                                                     dispatches)},
        },
        "speedups": {
            "warm_vs_interpreted_p50": round(
                _pcts(interp)["p50_ms"]
                / max(_pcts(warm)["p50_ms"], 1e-9), 2),
            "warm_vs_cold_p50": round(
                _pcts(cold)["p50_ms"]
                / max(_pcts(warm)["p50_ms"], 1e-9), 2),
            "batched_vs_sequential_p99": round(
                _pcts(seq_lat)["p99_ms"]
                / max(_pcts(bat_lat)["p99_ms"], 1e-9), 2),
        },
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_BATCH.json"), "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps(out["summary"]))
    return 1 if mismatch[0] else 0


def _set_planner(db, mode: str) -> None:
    """Flip one loaded engine between planner modes (the arm sweep
    mutates flags on a single 21M store exactly like the tier-oracle
    passes below)."""
    from dgraph_tpu.query.planner import AdaptivePlanner
    db.planner = mode
    db.planner_impl = AdaptivePlanner(db) if mode == "adaptive" \
        else None


def main_planner() -> int:
    """--planner: adaptive planner vs every statically pinned tier
    configuration on the identical workload + store.

    Arms (all host-path; the device arm is the main run's business):
      adaptive          cost-based per-stage tier choice, decisions
                        cached on plans, self-corrected
      static            the pre-PR-13 flag heuristics, all tiers on
                        (the incumbent default)
      static-columnar   compressed pinned off (dense CSR tier)
      static-postings   columnar pinned off (the exact-postings
                        oracle pin)

    Each arm gets its own warm-up passes (the adaptive arm's warm-up
    is also its training traffic — that is the design, the planner
    learns from exactly the traffic it serves). Parity: every arm's
    data payload must be byte-identical. The acceptance read-out:
    adaptive mixed-workload p50 >= best static pin, and the queries
    where adaptive beats EVERY pin. Results land under "planner" in
    BENCH_QUERIES.json (the main summary stays the device-vs-host
    run's)."""
    import numpy as np

    from bench import init_backend
    from dgraph_tpu.utils import coststore

    devs, platform = init_backend()
    sys.stderr.write(f"jax devices: {devs} (platform={platform})\n")
    scale = SCALE
    repeats = max(REPEATS, 5)  # arm deltas are small: steadier p50s
    workload = load_workload(scale)
    db, n_rdf = build_db(scale, prefer_device=False)

    arms = [
        ("adaptive", "adaptive", True, True),
        ("static", "static", True, True),
        ("static-columnar", "static", True, False),
        ("static-postings", "static", False, False),
    ]

    adaptive_planner = None

    def _set_arm(name):
        nonlocal adaptive_planner
        _, mode, columnar, compressed = next(
            a for a in arms if a[0] == name)
        db.prefer_columnar = columnar
        db.prefer_compressed = compressed
        if mode == "adaptive":
            # ONE planner instance across the whole sweep: its
            # learned estimates / re-optimized decisions are the
            # adaptive arm's state
            if adaptive_planner is None:
                _set_planner(db, "adaptive")
                adaptive_planner = db.planner_impl
            else:
                db.planner = "adaptive"
                db.planner_impl = adaptive_planner
        else:
            db.planner = "static"
            db.planner_impl = None

    # global warm-up (JIT, column caches, tile LRU) outside any arm.
    # The static pins run FIRST: their stage spans land in the
    # process-global coststore stamped with each pin's tier, so by
    # the time the adaptive arm trains, every tier has observed cells
    # — the production shape (a planner deployed on an engine with
    # traffic history adapts immediately; a greenfield one converges
    # via its own fallback observations and rival checks). Then the
    # adaptive arm's training traffic (the planner learns from
    # exactly the traffic it serves — that IS the design).
    coststore.reset()
    for name, _m, _c, _x in arms[1:]:
        _set_arm(name)
        for _ in range(4):
            for _n, q in workload:
                db.query(q)
    _set_arm("adaptive")
    for _ in range(5):
        for _n, q in workload:
            db.query(q)
    # timing: per QUERY, arms interleaved, min-of-K floors. At this
    # regime per-request times are fractions of a millisecond and
    # box noise (GC pauses, CPU steal) is ±10% per shot — medians of
    # widely spaced single shots measure the noise, not the routing.
    # The min over K back-to-back runs per (query, arm, round) is
    # each arm's steady-state floor on that query — exactly what tier
    # routing controls — and interleaving arms inside each query
    # keeps any drift fair.
    K = 3
    times = {name: {n: [] for n, _ in workload} for name, *_ in arms}
    outputs: dict[str, dict] = {}
    for n, q in workload:
        for r in range(repeats):
            # rotate the arm order per round: whichever arm runs
            # first after a query switch pays its cold costs — no arm
            # gets to always be second
            order = arms[r % len(arms):] + arms[:r % len(arms)]
            for name, *_rest in order:
                _set_arm(name)
                for _k in range(K):
                    t = time.perf_counter()
                    got = db.query(q)
                    times[name][n].append(time.perf_counter() - t)
                if r == 0:
                    outputs.setdefault(name, {})[n] = json.dumps(
                        got["data"], sort_keys=True)
    _set_arm("adaptive")
    planner_stats = dict(db.planner_impl.stats())

    # parity across every arm, all 77 shapes
    base = outputs["adaptive"]
    mismatched = sorted(
        {n for n in base
         for arm in outputs if outputs[arm][n] != base[n]})
    # per-query floor (min over all interleaved shots), then the
    # mixed-workload summary = median of per-query floors
    p50 = {
        arm: {n: float(np.min(ts)) * 1e3
              for n, ts in times[arm].items()} for arm in times}
    mix50 = {arm: round(float(np.median(
        list(p50[arm].values()))), 4) for arm in times}
    static_arms = [a for a in p50 if a != "adaptive"]
    best_static = min(mix50[a] for a in static_arms)
    # wins: shapes where adaptive's floor strictly beats EVERY pin's
    # (the per-shape spread between tiers at this regime is a few
    # percent, so a wide noise margin would define wins away;
    # wins_margin_5pct is the conservative count, and the full
    # per-query table is committed for recomputation)
    wins = []
    wins_5pct = 0
    for n, _q in workload:
        ours = p50["adaptive"][n]
        best_pin = min(p50[a][n] for a in static_arms)
        if ours < best_pin:
            wins.append({"query": n, "adaptive_ms": round(ours, 3),
                         "best_static_ms": round(best_pin, 3),
                         "speedup": round(best_pin / max(ours, 1e-9),
                                          3)})
            if ours < 0.95 * best_pin:
                wins_5pct += 1
    wins.sort(key=lambda w: -w["speedup"])
    # the practically-felt wins: vs the DEFAULT static configuration
    # (what the engine would otherwise do), 10% margin
    wins_vs_default = sorted(
        ({"query": n, "adaptive_ms": round(p50["adaptive"][n], 3),
          "static_ms": round(p50["static"][n], 3),
          "speedup": round(p50["static"][n]
                           / max(p50["adaptive"][n], 1e-9), 2)}
         for n, _q in workload
         if p50["adaptive"][n] < 0.9 * p50["static"][n]),
        key=lambda w: -w["speedup"])
    regressions = []
    for n, _q in workload:
        ours = p50["adaptive"][n]
        best_pin = min(p50[a][n] for a in static_arms)
        if ours > 1.05 * best_pin:
            regressions.append(
                {"query": n, "adaptive_ms": round(ours, 3),
                 "best_static_ms": round(best_pin, 3),
                 "slowdown": round(ours / max(best_pin, 1e-9), 2)})
    regressions.sort(key=lambda w: (w["best_static_ms"]
                                    - w["adaptive_ms"]))
    for r in regressions[:8]:
        sys.stderr.write(f"regression: {r}\n")
    out = {
        "metric": f"planner_mix_p50_ms_{n_rdf//1_000_000}M",
        "value": mix50["adaptive"],
        "unit": "ms",
        "vs_baseline": round(best_static
                             / max(mix50["adaptive"], 1e-9), 3),
        "platform": platform, "scale": scale, "rdf": n_rdf,
        "repeats": repeats,
        "parity_ok": not mismatched,
        "mismatched": mismatched[:10],
        "mix_p50_ms": mix50,
        "at_least_parity": mix50["adaptive"] <= best_static * 1.02,
        "adaptive_wins_all_pins": len(wins),
        "wins_margin_5pct": wins_5pct,
        "wins": wins[:10],
        "wins_vs_default": wins_vs_default[:10],
        "regressions": regressions[:10],
        "planner": planner_stats,
        "per_query_p50_ms": {
            arm: {n: round(v, 4) for n, v in p50[arm].items()}
            for arm in p50},
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_QUERIES.json")
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {}
    doc["planner"] = out
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(json.dumps({k: out[k] for k in (
        "metric", "value", "unit", "vs_baseline", "parity_ok",
        "at_least_parity", "adaptive_wins_all_pins", "mix_p50_ms")}))
    return 1 if mismatched else 0


def main():
    import numpy as np

    from bench import init_backend

    devs, platform = init_backend()
    sys.stderr.write(f"jax devices: {devs} (platform={platform})\n")
    scale = SCALE

    workload = load_workload(scale)
    sys.stderr.write(f"workload: {len(workload)} queries\n")

    db, n_rdf = build_db(scale, prefer_device=True)

    # warm the device tier (tile upload + XLA compiles) outside timing
    t0 = time.time()
    for name, q in workload:
        db.query(q)
    sys.stderr.write(f"device warmup pass {time.time()-t0:.0f}s\n")

    # snapshot the counter registry AROUND the device run so
    # device_counters reports exactly the measured workload's tier
    # routing (the whole-process snapshot it replaced was drowned by
    # warmup/load counters and filtered down to nothing)
    from dgraph_tpu.utils.metrics import snapshot
    before = snapshot()["counters"]
    dev = run_workload(db, workload, REPEATS)
    dev_out = dev.pop("__outputs__")
    after = snapshot()["counters"]
    dev_counters = {
        k: after[k] - before.get(k, 0) for k in sorted(after)
        if k.startswith("query_") and after[k] != before.get(k, 0)}

    db.prefer_device = False  # same store, host-only executor path
    host = run_workload(db, workload, REPEATS)
    host_out = host.pop("__outputs__")

    # resident posting bytes at the regime, measured while the
    # compressed tier's exports are warm from the runs above and
    # BEFORE the oracle passes below can disturb the caches
    resident = _measure_resident(db)

    # the columnar tier must be byte-identical to the per-posting
    # path, clean-store case (the differential test covers dirty)
    db.prefer_columnar = False
    postings = run_workload(db, workload, 1)
    postings_out = postings.pop("__outputs__")
    db.prefer_columnar = True

    # dense-tier oracle: compressed OFF must also match byte-for-byte
    db.prefer_compressed = False
    dense_tier = run_workload(db, workload, 1)
    dense_out = dense_tier.pop("__outputs__")
    db.prefer_compressed = True

    mismatched = sorted(
        n for n in dev_out
        if dev_out[n] != host_out[n] or dev_out[n] != postings_out[n]
        or dev_out[n] != dense_out[n])

    # encode ms/op at ~100k rows (VERDICT r2 item 6): the columnar
    # native emitter (query_json) vs the dict+json.dumps loop, on a
    # six-figure flat result from the loaded graph
    enc = _measure_encode_100k(db, scale)

    detail = {}
    for name, _ in workload:
        detail[name] = {
            "device_p50_ms": round(
                float(np.median(dev[name])) * 1e3, 2),
            "host_p50_ms": round(
                float(np.median(host[name])) * 1e3, 2),
        }
    dev_all = [t for name, _ in workload for t in dev[name]]
    host_all = [t for name, _ in workload for t in host[name]]
    dev_p50 = float(np.median(dev_all)) * 1e3
    host_p50 = float(np.median(host_all)) * 1e3
    dev_qps = len(dev_all) / sum(dev_all)
    host_qps = len(host_all) / sum(host_all)

    summary = {
        "metric": f"query_surface_p50_ms_{n_rdf//1_000_000}M",
        "value": round(dev_p50, 2),
        "unit": "ms",
        "vs_baseline": round(host_p50 / dev_p50, 3),
        "device_qps": round(dev_qps, 1),
        "host_qps": round(host_qps, 1),
        "queries": len(workload),
        "repeats": REPEATS,
        "scale": scale,
        "rdf": n_rdf,
        "parity_ok": not mismatched,
        "mismatched": mismatched,
        "platform": platform,
        "encode_100k": enc,
        "resident_bytes": resident,
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_QUERIES.json"), "w") as f:
        json.dump({"summary": summary, "device_counters": dev_counters,
                   "per_query": detail}, f, indent=1, sort_keys=True)
    print(json.dumps(summary))
    return 1 if mismatched else 0


if __name__ == "__main__":
    if "--concurrency" in sys.argv:
        n = int(sys.argv[sys.argv.index("--concurrency") + 1])
        sys.exit(main_concurrency(n))
    if "--planner" in sys.argv:
        sys.exit(main_planner())
    sys.exit(main())
