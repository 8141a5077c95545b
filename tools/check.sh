#!/usr/bin/env bash
# Repo static-check gate: run before pushing (tier-1 also enforces the
# dglint gate via tests/test_dglint.py).
#
#   1. dglint        — project invariant linter (tools/dglint) in
#                      whole-program mode (call-graph rules DG10-12),
#                      vs the committed baseline, which must be EMPTY
#                      (--assert-empty-baseline: no grandfathered tech
#                      debt). --changed-only re-lints only files whose
#                      content hash moved (manifest:
#                      tools/.dglint_cache.json); the whole-program
#                      rules still analyze every file's summary
#   2. compileall    — every file byte-compiles (syntax gate; dglint
#                      skips unparseable files, so this owns them)
#   3. import sweep  — `import dgraph_tpu` under -W error for
#                      DeprecationWarning: dependency API drift
#                      (jax/numpy renames) surfaces here first, not as
#                      a tier-1 collection error three releases later
#
# Every gate below runs on the CPU backend (JAX_PLATFORMS defaults to
# cpu here): they check bytes, counts and host-side budgets. Whether
# the program starts and answers correctly on a chip is chip_smoke.py's
# question, asked on a machine that has one.
#
# Usage: tools/check.sh          (from the repo root)

set -euo pipefail
cd "$(dirname "$0")/.."

echo "== dglint (whole-program, incremental) =="
python -m tools.dglint --changed-only --assert-empty-baseline \
    dgraph_tpu tests

echo "== compileall =="
python -m compileall -q dgraph_tpu tests tools bench.py bench_micro.py \
    bench_queries.py bench_vectors.py chip_smoke.py __graft_entry__.py

echo "== import-warnings sweep =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -W error::DeprecationWarning -c "import dgraph_tpu"

echo "== plan-cache smoke =="
# compile one skeleton, assert the second run hits with zero retrace
# (silent cache-key regressions surface as p99 cliffs, not failures)
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.plan_smoke

echo "== fusion smoke =="
# whole-plan fused tier: engages, byte-matches the staged chain,
# stamps honest fallback attributions, zero-recompile on param replay
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.fusion_smoke

echo "== cold-store smoke =="
# miniature BENCH_500M: bulk-seeded store reopened under tablet-budget
# pressure with async prefetch on; fused == staged == postings oracle
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.coldstore_smoke

echo "== span overhead =="
# per-span tracing cost vs the 5 µs budget (spans sit on executor hot
# paths; tests/test_tracing.py enforces the same budget with CI slack)
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python bench_micro.py --span-overhead

echo "== stats overhead =="
# the always-on statistics plane (coststore span observer + tablet
# touch counters) must cost < 1% on the golden summary workload;
# non-zero exit = over budget (DGRAPH_TPU_STATS_BUDGET overrides)
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python bench_micro.py --stats-overhead

echo "== planner overhead + smoke =="
# adaptive-planner decision cost (consults x warm per-consult cost)
# must stay < 1% of the summary mix, AND a warm pass must serve every
# tier decision from the plan cache (zero rebuilds after convergence)
# — non-zero exit on either (DGRAPH_TPU_PLANNER_BUDGET overrides)
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python bench_micro.py --planner-overhead

echo "== ann smoke =="
# ~5 s quantized vector tier gate (tools/ann_smoke.py): train + query
# on a small seeded corpus — index trains at rollup, similar_to routes
# quantized, recall floor vs the exact oracle, MVCC overlay parity at
# old/new read_ts, codebook snapshot round-trip byte-deterministic.
# The vector_* metrics and the vecstore.build failpoint site are
# DG08-registered (utils/metrics.py REGISTERED, utils/failpoint.py
# SITES), so the dglint step above already gates their names.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.ann_smoke

echo "== pprof overhead =="
# the on-demand sampling profiler at its default 100 Hz must cost
# < 2% of throughput while active (decomposed per-sample x rate gate;
# DGRAPH_TPU_PPROF_BUDGET overrides)
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python bench_micro.py --pprof-overhead

echo "== netfault overhead =="
# the DISARMED network-fault seam on the wire hot paths (one
# falsy-dict check per send) must cost < 1% of the summary mix
# (decomposed gate; DGRAPH_TPU_NETFAULT_BUDGET overrides)
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python bench_micro.py --netfault-overhead

echo "== racecheck overhead =="
# the ARMED attribute-access race witness (utils/racecheck, the
# `racecheck` marker the tier-1 concurrency suites run under) must
# cost < 5% of the summary mix (decomposed: per-sampled-access cost
# x nominal accesses/op; DGRAPH_TPU_RACECHECK_BUDGET overrides)
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python bench_micro.py --racecheck-overhead

echo "== watchdog overhead =="
# the always-on alerting plane (watchdog evaluator tick + the reqlog
# observer feeding the SLO burn windows) must cost < 1% of the
# summary mix (decomposed: tick duty cycle + per-observation cost;
# DGRAPH_TPU_WATCHDOG_BUDGET overrides)
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python bench_micro.py --watchdog-overhead

echo "== compressed setops =="
# compressed-vs-dense set algebra sweep: block-descriptor skipping
# must beat decode-then-intersect on the selective-intersection
# config, with full result parity (DGRAPH_TPU_SETOPS_BUDGET overrides)
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python bench_micro.py --setops-compressed

echo "== cluster load smoke =="
# ~30 s mini-cluster open-loop run (1 zero + 2 single-replica groups,
# tiny seeded graph, gentle fixed rate) through tools/dgbench.py:
# asserts ZERO non-shed errors, p99 under a generous budget, and
# byte-parity of under-load reads vs a sequential replay. The run
# report (per-node logs, /debug scrapes, a dgtop --once snapshot) is
# the archived cluster-state artifact.
SMOKE_DIR="${TMPDIR:-/tmp}/dgbench-smoke"
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.dgbench --smoke \
    --report-dir "$SMOKE_DIR" --out "$SMOKE_DIR/BENCH_SMOKE.json"
test -s "$SMOKE_DIR/dgtop.txt"   # the archived cluster-state artifact
echo "smoke report: $SMOKE_DIR"

echo "== ingest smoke =="
# ~30 s distributed-ingest gate (tools/dgingest.py --smoke): a small
# seeded workload through the map→shuffle→reduce pipeline at 2 groups
# x 2 workers, reduced shards BOOTED as a real ProcessCluster via
# `node --snapshot`, and every golden read byte-compared against the
# single-core bulk_load oracle. Exit non-zero on any parity mismatch.
INGEST_DIR="${TMPDIR:-/tmp}/dgingest-smoke"
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.dgingest --smoke \
    --report-dir "$INGEST_DIR" --out "$INGEST_DIR/BENCH_INGEST.json"
test -s "$INGEST_DIR/BENCH_INGEST.json"

echo "== cdc smoke =="
# ~5 s change-stream gate (tools/cdc_smoke.py): subscribe -> mutate ->
# replay-from-offset x2 byte check, long-poll heartbeat + wakeup,
# mid-stream resume, and subscriber lag on /debug/stats
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.cdc_smoke

echo "== scaleout smoke =="
# ~30 s read scale-out gate (tools/scaleout_smoke.py): embedded
# result-cache byte parity under churn (cached hit == uncached oracle,
# footprint isolation), then a live 1 voter + 1 learner cluster —
# learner conf-joins non-voting, serves the voter's exact bytes at one
# zero-granted read_ts, best-effort reads observe fresh commits, and
# per-tenant QoS sheds a hot tenant without touching a quiet one.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.scaleout_smoke

echo "== rebalance smoke =="
# ~30 s heat-driven rebalancing gate (tools/rebalance_smoke.py): a
# deliberately skewed 2-group cluster under live open load; the
# zero-side rebalancer must propose AND complete >=1 automatic tablet
# move with ZERO load errors across the cutover and byte-parity of
# final reads vs a quiesced single-process oracle replaying exactly
# the acknowledged mutations.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.rebalance_smoke

echo "== dr smoke =="
# ~30 s disaster-recovery gate (tools/dr_smoke.py): point-in-time
# restore byte-parity vs the full-log oracle at >= 3 non-boundary
# commit_ts, a REAL standby cluster tailing a live primary to lag 0,
# and a measured-RPO/RTO promotion (clean: zero acked commits lost,
# old primary fenced). Exit non-zero on any parity/RPO/fence failure.
DR_DIR="${TMPDIR:-/tmp}/dr-smoke"
rm -rf "$DR_DIR"
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.dr_smoke \
    --report-dir "$DR_DIR" --out "$DR_DIR/BENCH_DR.json"
test -s "$DR_DIR/BENCH_DR.json"

echo "== chaos smoke =="
# ~45 s nemesis cycle on a 2-group mini cluster with durable dirs
# (tools/dgchaos.py --smoke): one partition-heal + one SIGKILL-restart
# under open-loop bank load; exits non-zero on ANY history-checker
# violation (conservation / monotonic ts / acked-write loss / lost
# update) or a non-finite time-to-recover after heal.
CHAOS_DIR="${TMPDIR:-/tmp}/dgchaos-smoke"
rm -rf "$CHAOS_DIR"   # durable dirs + history are per-run state
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.dgchaos --smoke \
    --report-dir "$CHAOS_DIR" --out "$CHAOS_DIR/BENCH_CHAOS.json"
test -s "$CHAOS_DIR/history.jsonl"   # the checked per-op history
echo "chaos report: $CHAOS_DIR"

echo "ok"
