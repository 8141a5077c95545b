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
# cpu here): they check bytes and counts, and time nothing — what an
# always-on plane costs is read from a benchmark cell with it on
# against off (benchmark/run.py, PERF_LEDGER.jsonl). Whether the
# program starts and answers correctly on a chip is chip_smoke.py's
# question, asked on a machine that has one.
#
# Usage: tools/check.sh          (from the repo root)

set -euo pipefail
cd "$(dirname "$0")/.."

echo "== dglint (whole-program, incremental) =="
python -m tools.dglint --changed-only --assert-empty-baseline \
    dgraph_tpu tests

echo "== compileall =="
python -m compileall -q dgraph_tpu tests tools benchmark bench_micro.py \
    chip_smoke.py __graft_entry__.py

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
# bulk-seeded store reopened under tablet-budget pressure with async
# prefetch on; fused == staged == postings oracle
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.coldstore_smoke

echo "== ann smoke =="
# ~5 s quantized vector tier gate (tools/ann_smoke.py): train + query
# on a small seeded corpus — index trains at rollup, similar_to routes
# quantized, recall floor vs the exact oracle, MVCC overlay parity at
# old/new read_ts, codebook snapshot round-trip byte-deterministic.
# The vector_* metrics and the vecstore.build failpoint site are
# DG08-registered (utils/metrics.py REGISTERED, utils/failpoint.py
# SITES), so the dglint step above already gates their names.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.ann_smoke

echo "== cluster load smoke =="
# ~30 s mini-cluster open-loop run (1 zero + 2 single-replica groups,
# tiny seeded graph, gentle fixed rate) through tools/dgbench.py:
# asserts ZERO non-shed errors, p99 under a generous budget, and
# byte-parity of under-load reads vs a sequential replay. The run
# report (per-node logs, /debug scrapes, a dgtop --once snapshot) is
# the archived cluster-state artifact.
SMOKE_DIR="${TMPDIR:-/tmp}/dgbench-smoke"
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.dgbench --smoke \
    --report-dir "$SMOKE_DIR" --out "$SMOKE_DIR/report.json"
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
    --report-dir "$INGEST_DIR" --out "$INGEST_DIR/report.json"
test -s "$INGEST_DIR/report.json"

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
# zero-granted read_ts, best-effort reads observe fresh commits, a
# SIGSTOPped learner refuses and never serves an older state, and
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
    --report-dir "$DR_DIR" --out "$DR_DIR/report.json"
test -s "$DR_DIR/report.json"

echo "== chaos smoke =="
# ~45 s nemesis cycle on a 2-group mini cluster with durable dirs
# (tools/dgchaos.py --smoke): one partition-heal + one SIGKILL-restart
# under open-loop bank load; exits non-zero on ANY history-checker
# violation (conservation / monotonic ts / acked-write loss / lost
# update) or a non-finite time-to-recover after heal.
CHAOS_DIR="${TMPDIR:-/tmp}/dgchaos-smoke"
rm -rf "$CHAOS_DIR"   # durable dirs + history are per-run state
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.dgchaos --smoke \
    --report-dir "$CHAOS_DIR" --out "$CHAOS_DIR/report.json"
test -s "$CHAOS_DIR/history.jsonl"   # the checked per-op history
echo "chaos report: $CHAOS_DIR"

echo "ok"
