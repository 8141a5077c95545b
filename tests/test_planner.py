"""Cost-based adaptive planner (query/planner.py): decision model,
plan-cached decisions, violation/drift re-optimization with bounded
re-plan rate, EXPLAIN surface, flag demotion — plus the coststore
estimate/age/drift API and the tabstats token histogram it reads."""

import pytest

from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.query.plan import Plan
from dgraph_tpu.query.planner import (
    EXPLORE_BURST, REPLAN_BURST, STATIC_PRIORS, AdaptivePlanner,
    token_quantile,
)
from dgraph_tpu.utils import coststore, metrics


class _StubDB:
    """The only engine surface the planner touches."""

    # off by default so the ladder/margin/rival tests below exercise
    # the DECISION model in isolation; the exploration tests flip it
    planner_explore = False

    def device_dispatch_seconds(self) -> float:
        return 0.01  # 10 ms: a deliberately expensive dispatch


def _plan(h: int = 0xABCD) -> Plan:
    return Plan(("q",), h, 0, None)


def _warm(stage: str, tier: str, skel: str, bucket: int,
          dur_us: float, n: int = 10):
    for _ in range(n):
        coststore.record(stage, tier, skel, bucket, dur_us)


@pytest.fixture
def pl():
    coststore.reset()
    metrics.reset()
    yield AdaptivePlanner(_StubDB())
    coststore.reset()


EST = {"estRows": 64, "estRowsMax": 1024, "basis": "stats"}
IDX = ("postings", "columnar", "compressed")


# ------------------------------------------------------- cost model


def test_priors_keep_static_ladder_cold():
    """The ordering invariant the module documents: with cold cells,
    compressed <= columnar <= postings at EVERY row count, so a cold
    planner reproduces the static flag routing exactly."""
    for stage in ("eq", "setops"):
        for n in (0, 1, 10, 1_000, 1_000_000):
            def cost(tier):
                f, p = STATIC_PRIORS[(stage, tier)]
                return f + p * n
            assert cost("compressed") <= cost("columnar") \
                <= cost("postings"), (stage, n)
    # every routed (stage, tier) pair has a documented prior
    for key in (("ineq", "device"), ("ineq", "columnar"),
                ("ineq", "postings"), ("sort", "device"),
                ("sort", "columnar"), ("sort", "postings"),
                ("similar_to", "device"), ("similar_to", "postings")):
        assert key in STATIC_PRIORS


def test_cold_choice_is_compressed(pl):
    dec = pl.choose(_plan(), "eq", "name", EST, IDX)
    assert dec.tier == "compressed"
    assert dec.basis == "prior"
    assert dec.version == 0 and not dec.describe()["reoptimized"]
    assert set(dec.costs) == set(IDX)


def test_warm_observed_cells_override_priors(pl):
    plan = _plan(0x1111)
    skel = f"{plan.skeleton_hash:016x}"
    bucket = 64 .bit_length()
    _warm("eq", "compressed", skel, bucket, 500.0)  # observed slow
    _warm("eq", "columnar", skel, bucket, 5.0)      # observed fast
    dec = pl.choose(plan, "eq", "name", EST, IDX)
    assert dec.tier == "columnar"
    assert dec.basis == "observed"


def test_single_observed_tier_needs_margin_to_lose(pl):
    """One-sided evidence: an observed tier that loses to a PRIOR by
    less than 2x keeps serving (priors are guesses); past 2x the
    ladder takes over."""
    plan = _plan(0x2222)
    skel = f"{plan.skeleton_hash:016x}"
    bucket = 64 .bit_length()
    # compressed observed at 8µs vs columnar prior ~7.3µs: within
    # margin, observed tier keeps the route
    _warm("eq", "compressed", skel, bucket, 8.0)
    dec = pl.choose(plan, "eq", "name", EST, IDX)
    assert dec.tier == "compressed"
    coststore.reset()
    # compressed observed at 100x the columnar prior: deviate
    _warm("eq", "compressed", skel, bucket, 700.0)
    dec = pl.choose(_plan(0x2223), "eq", "name", EST, IDX)
    assert dec.tier == "columnar"
    assert dec.basis == "mixed"


def test_device_pays_dispatch_rtt(pl):
    """The measured dispatch RTT rides every device cost estimate: a
    10ms dispatch keeps small stages off the device whatever the
    priors say."""
    dec = pl.choose(_plan(0x3333), "ineq", "age", EST,
                    ("postings", "columnar", "device"))
    assert dec.tier != "device"
    assert dec.costs["device"] >= 10_000.0


# ----------------------------------------- decision cache + re-plan


def test_decision_cached_on_plan(pl):
    plan = _plan(0x4444)
    d1 = pl.choose(plan, "eq", "name", EST, IDX)
    d2 = pl.choose(plan, "eq", "name", EST, IDX)
    assert d1 is d2
    assert pl.stats()["decisions"] == 1
    assert pl.stats()["consults"] == 2


def test_violation_learns_and_reoptimizes(pl):
    plan = _plan(0x5555)
    d1 = pl.choose(plan, "eq", "name", EST, IDX)
    # actual lands 3+ buckets from the estimate: violation
    pl.record_outcome(d1, 5_000)
    st = pl.stats()
    assert st["estimateViolations"] == 1
    assert st["reoptimized"] == 1
    d2 = pl.choose(plan, "eq", "name", EST, IDX)
    assert d2 is not d1
    assert d2.version == 1
    assert d2.est_basis == "learned"
    assert d2.est_rows == 5_000
    assert d2.describe()["reoptimized"] is True
    # converged: the learned estimate matches reality, no more churn
    pl.record_outcome(d2, 5_000)
    d3 = pl.choose(plan, "eq", "name", EST, IDX)
    assert d3 is d2


def test_replan_rate_is_bounded(pl):
    plan = _plan(0x6666)
    dec = pl.choose(plan, "eq", "name", EST, IDX)
    for _ in range(REPLAN_BURST + 6):
        pl.record_outcome(dec, 1_000_000)  # violating forever
    st = pl.stats()
    assert st["reoptimized"] == REPLAN_BURST
    assert st["replansSuppressed"] == 6
    c = metrics.counters_snapshot()
    assert c.get("planner_replans_suppressed_total") == 6


def test_rival_tier_invalidates_sampled(pl):
    """Cost drift's other direction: the chosen tier's own EWMA is
    steady, but a warm ALTERNATIVE's observed cost undercuts it —
    the cached cold-prior decision must be revisited."""
    plan = _plan(0x7878)
    skel = f"{plan.skeleton_hash:016x}"
    bucket = 64 .bit_length()
    dec = pl.choose(plan, "eq", "name", EST, IDX)
    assert dec.tier == "compressed"  # cold ladder
    _warm("eq", "compressed", skel, bucket, 50.0, n=30)
    _warm("eq", "columnar", skel, bucket, 10.0, n=30)
    for _ in range(16):
        pl.record_outcome(dec, 64)
    assert pl.stats()["reoptimized"] >= 1
    d2 = pl.choose(plan, "eq", "name", EST, IDX)
    assert d2.tier == "columnar" and d2.basis == "observed"


def test_drift_invalidates_sampled(pl):
    plan = _plan(0x7777)
    skel = f"{plan.skeleton_hash:016x}"
    bucket = 64 .bit_length()
    _warm("eq", "compressed", skel, bucket, 10.0, n=30)
    dec = pl.choose(plan, "eq", "name", EST, IDX)
    assert dec.tier == "compressed"
    # the tier's cost quadruples: fast EWMA runs away from slow
    _warm("eq", "compressed", skel, bucket, 500.0, n=10)
    assert coststore.drift("eq", "compressed", bucket, skel) > 2.0
    for _ in range(16):  # sampling boundaries trigger the check
        pl.record_outcome(dec, 64)
    assert pl.stats()["reoptimized"] >= 1
    d2 = pl.choose(plan, "eq", "name", EST, IDX)
    assert d2.version >= 1


# ------------------------------------------------------ exploration


def test_exploration_never_fires_cold_cold(pl):
    """With NO evidence at all the static ladder stays authoritative:
    exploration needs a warm cell to compare against."""
    pl.db.planner_explore = True
    dec = pl.choose(_plan(0x5252), "eq", "name", EST, IDX)
    assert dec.basis == "prior" and dec.tier == "compressed"
    assert pl.stats()["explored"] == 0


def test_exploration_probes_cold_tier_then_rejudges(pl):
    """One warm tier + one cold tier within margin: the cold tier gets
    ONE budgeted probe (basis 'explored'); its outcome lands the first
    cost cell and the next choose re-judges on two-sided evidence."""
    pl.db.planner_explore = True
    plan = _plan(0x5151)
    skel = f"{plan.skeleton_hash:016x}"
    bucket = 64 .bit_length()
    avail = ("columnar", "compressed")
    _warm("eq", "compressed", skel, bucket, 8.0)
    dec = pl.choose(plan, "eq", "name", EST, avail)
    assert dec.basis == "explored" and dec.tier == "columnar"
    assert pl.stats()["explored"] == 1
    # the probe served: its stage span lands columnar's first cell,
    # and record_outcome invalidates the explored decision outright
    _warm("eq", "columnar", skel, bucket, 4.0)
    pl.record_outcome(dec, 64)
    d2 = pl.choose(plan, "eq", "name", EST, avail)
    assert d2.basis == "observed" and d2.tier == "columnar"


def test_exploration_budget_bounds_probes(pl):
    """A probe that never lands evidence (the explored tier's spans go
    unrecorded) retries only while the per-key token bucket has
    budget, then the normal decision takes over."""
    pl.db.planner_explore = True
    plan = _plan(0x5353)
    skel = f"{plan.skeleton_hash:016x}"
    _warm("eq", "compressed", skel, 64 .bit_length(), 8.0)
    bases = []
    for _ in range(4):
        dec = pl.choose(plan, "eq", "name", EST,
                        ("columnar", "compressed"))
        bases.append(dec.basis)
        pl.record_outcome(dec, 64)
    assert bases.count("explored") == EXPLORE_BURST
    assert bases[-1] == "observed"
    assert pl.stats()["explored"] == EXPLORE_BURST


# --------------------------------------------- plan-level decisions


def test_probe_or_scan_pivot(pl):
    # tiny candidate set vs a huge estimated probe: scan
    assert pl.probe_or_scan("eq", 100_000, 10) == "scan"
    # big candidate set vs a small probe: probe
    assert pl.probe_or_scan("eq", 50, 10_000) == "probe"


def test_gallop_ratio_density_pivot():
    assert AdaptivePlanner.gallop_ratio(10, 10_000) == 4    # sparse
    assert AdaptivePlanner.gallop_ratio(900, 1_000) == 16   # dense
    assert AdaptivePlanner.gallop_ratio(100, 10_000) == 16  # middle
    assert AdaptivePlanner.gallop_ratio(0, 0) == 16


def test_token_quantile_reads_histogram():
    # 10 tokens of length 1 (bucket 1), one hot token ~100k (bucket 17)
    hist = [0] * 21
    hist[1] = 10
    hist[17] = 1
    ti = {"hist": hist, "avgPostings": 3.0, "maxPostings": 100_000}
    assert token_quantile(ti, 0.75) == 1.5   # the q75 token is tiny
    assert token_quantile(ti, 0.99) > 50_000  # the tail is the hot one
    # no histogram: fall back to the tablet-wide mean
    assert token_quantile({"avgPostings": 3.0}, 0.75) == 3.0


# ------------------------------------------------- engine end-to-end


SCHEMA = """
name: string @index(term, exact) .
age: int @index(int) .
"""


def _engine(**kw) -> GraphDB:
    db = GraphDB(prefer_device=False, **kw)
    db.alter(schema_text=SCHEMA)
    quads = []
    for i in range(1, 301):
        # every name shares the term "hot"; everything else is unique
        # -> the q75 per-token estimate is tiny, the hot probe is not:
        # the planted mis-estimate
        quads.append(f'<0x{i:x}> <name> "hot u{i}" .')
        quads.append(f'<0x{i:x}> <age> "{i % 77}" .')
    db.mutate(set_nquads="\n".join(quads))
    db.rollup_all()
    return db


def test_invalid_planner_arg():
    with pytest.raises(ValueError, match="planner must be"):
        GraphDB(planner="fancy")


def test_static_mode_has_no_planner():
    db = _engine(planner="static")
    assert db.planner == "static" and db.planner_impl is None
    resp = db.query('{ q(func: eq(name, "hot u1")) { uid } }',
                    explain="plan")
    e = resp["extensions"]["explain"]
    assert e["tiers"]["planner"] == "static"
    assert e["tierDecisions"] == []


def test_a_known_count_is_not_overridden_by_a_learned_guess(pl):
    plan = _plan(0x5656)
    d1 = pl.choose(plan, "eq", "name", EST, IDX)
    pl.record_outcome(d1, 5_000)            # the key learns 5,000
    exact = {"estRows": 40, "estRowsMax": 40, "basis": "exact"}
    d2 = pl.choose(plan, "eq", "name", exact, IDX)
    assert (d2.est_basis, d2.est_rows) == ("exact", 40)
    assert pl.choose(plan, "eq", "name", EST, IDX).est_basis == "learned"


def test_one_tokens_eq_is_decided_for_its_own_posting_length():
    """`eq(cat, $c)` over values whose postings differ 60-fold: one
    decision a size bucket, exact estimates, and no request violates
    an estimate made for another value (that flapped the tier of a
    whole stage key: sift1m-exact.knn-mix, PERF.md PR 26)."""
    coststore.reset()
    db = GraphDB(prefer_device=False, planner="adaptive")
    db.alter(schema_text="cat: int @index(int) .")
    sizes = {0: 1200, 1: 150, 2: 18}
    quads, uid = [], 0
    for c, n in sizes.items():
        for _ in range(n):
            uid += 1
            quads.append(f'<0x{uid:x}> <cat> "{c}" .')
    db.mutate(set_nquads="\n".join(quads))
    db.rollup_all()
    before = metrics.counters_snapshot()
    for _ in range(3):
        for c, n in sizes.items():
            r = db.query('{ q(func: eq(cat, %d)) { count(uid) } }' % c,
                         explain="analyze")
            assert r["data"]["q"][0]["count"] == n
            (d,) = [d for d in r["extensions"]["explain"]["tierDecisions"]
                    if d["stage"] == "eq"]
            assert (d["estRows"], d["estBasis"]) == (n, "exact")
            # one posting is a slice: the packs (a decode of the whole
            # list, no block to skip) are not a tier of this stage, so
            # a noisy span cannot drift the decision onto them
            assert "compressed" not in d["costUs"]
            assert d["tier"] in ("columnar", "postings")
    moved = metrics.counters_delta(before)
    assert moved.get("planner_estimate_violations_total", 0) == 0
    assert moved.get("query_compressed_setops_total", 0) == 0
    assert not any(k.startswith("planner_reoptimized_total")
                   and "violation" in k for k in moved)
    # two tokens are a set operation: there the packs stay on offer
    r = db.query('{ q(func: eq(cat, [0, 1])) { count(uid) } }',
                 explain="analyze")
    assert r["data"]["q"][0]["count"] == 1350
    (d,) = [d for d in r["extensions"]["explain"]["tierDecisions"]
            if d["stage"] == "eq"]
    assert "compressed" in d["costUs"]


def test_planted_misestimate_reoptimizes_and_converges():
    """The acceptance scenario: a Zipfian token breaks the histogram
    estimate -> EXPLAIN ANALYZE shows the violation counter move ->
    the SUBSEQUENT request re-optimized (reoptimized: true, learned
    basis) -> decisions settle (served from the plan cache)."""
    coststore.reset()
    db = _engine(planner="adaptive")
    q = '{ q(func: anyofterms(name, "hot")) { count(uid) } }'
    before = metrics.counters_snapshot()
    r1 = db.query(q, explain="analyze")
    delta = metrics.counters_delta(before)
    assert delta.get("planner_estimate_violations_total", 0) >= 1
    d1 = [d for d in r1["extensions"]["explain"]["tierDecisions"]
          if d["stage"] == "setops"]
    assert d1 and d1[0]["estRows"] < 300  # the planted under-estimate
    # subsequent request: re-optimized against the learned actual
    r2 = db.query(q, explain="analyze")
    d2 = [d for d in r2["extensions"]["explain"]["tierDecisions"]
          if d["stage"] == "setops"]
    assert d2[0]["reoptimized"] is True
    assert d2[0]["estBasis"] == "learned"
    assert d2[0]["version"] >= 1
    assert abs(d2[0]["estRows"] - 300) <= 1
    # converged: a further run builds nothing new — and with the
    # plan-routing warm layer it does not even CONSULT the planner
    # (the decision validates against the generation in a dict probe)
    st_before = db.planner_impl.stats()
    r3 = db.query(q, explain="plan")
    st_after = db.planner_impl.stats()
    assert st_after["decisions"] == st_before["decisions"]
    assert st_after["consults"] == st_before["consults"]
    # ...while EXPLAIN still reports the served decision
    d3 = [d for d in r3["extensions"]["explain"]["tierDecisions"]
          if d["stage"] == "setops"]
    assert d3 and d3[0]["estBasis"] == "learned"
    # both answers byte-identical along the way
    assert r1["data"] == r2["data"]


def test_flag_overrides_bound_the_planner():
    """prefer_columnar=False (the parity oracle pin) leaves the
    adaptive planner only the postings tier — flags demote to
    overrides, they still pin."""
    db = _engine(planner="adaptive", prefer_columnar=False)
    db.query('{ q(func: anyofterms(name, "hot")) { count(uid) } }')
    mix = db.planner_impl.stats()["mix"]
    tiers = {t for tiers in mix.values() for t in tiers}
    assert tiers <= {"postings"}


def test_debug_stats_carries_planner_and_cost_ages():
    db = _engine(planner="adaptive")
    db.query('{ q(func: eq(name, "hot u5")) { uid } }')
    st = db.debug_stats()
    assert st["planner"]["mode"] == "adaptive"
    assert st["planner"]["decisions"] >= 1
    assert "consults" in st["planner"]
    # coststore rows expose EWMA age (the cold/dead-cell signal)
    if st["cost"]:
        assert "ageS" in st["cost"][0]
        assert "drift" in st["cost"][0]
    assert "stalestAgeS" in st["costStore"]


def test_tabstats_token_histogram():
    db = _engine(planner="static")
    from dgraph_tpu.storage.tabstats import tablet_stats
    ti = tablet_stats(db.tablets["name"])["tokenIndex"]
    assert "hist" in ti and len(ti["hist"]) == 21
    # 300 unique "uN" term tokens + 300 exact tokens at length 1 in
    # bucket 1; the hot term token (300 postings) in bucket 9
    assert sum(ti["hist"]) == ti["tokens"]
    assert ti["hist"][9] >= 1
    assert ti["maxPostings"] == 300


# --------------------------------------------- coststore estimate API


def test_coststore_estimate_fallback_chain():
    coststore.reset()
    try:
        _warm("eq", "columnar", "aaaa", 7, 10.0)
        # exact cell
        got = coststore.estimate("eq", "columnar", 7, "aaaa")
        assert got["cell"] == "exact" and got["warm"]
        assert got["ewma_us"] == pytest.approx(10.0)
        assert got["age_s"] >= 0.0
        # other-skeleton, other-bucket: scaled per-row extrapolation
        got = coststore.estimate("eq", "columnar", 9, "bbbb")
        assert got["cell"] == "scaled"
        assert got["ewma_us"] == pytest.approx(40.0)  # 2^(9-7) x
        # never-observed tier: None -> caller uses priors
        assert coststore.estimate("eq", "device", 7, "aaaa") is None
        # cold cell is reported but flagged
        coststore.record("eq", "postings", "cccc", 3, 5.0)
        got = coststore.estimate("eq", "postings", 3, "cccc")
        assert got["cell"] == "exact" and not got["warm"]
    finally:
        coststore.reset()


def test_coststore_drift_signal():
    coststore.reset()
    try:
        assert coststore.drift("eq", "columnar", 5, "x") == 1.0  # cold
        _warm("eq", "columnar", "x", 5, 10.0, n=30)
        assert coststore.drift("eq", "columnar", 5, "x") == \
            pytest.approx(1.0, abs=0.2)
        _warm("eq", "columnar", "x", 5, 400.0, n=10)
        assert coststore.drift("eq", "columnar", 5, "x") > 2.0
    finally:
        coststore.reset()


def test_coststore_age_survives_save_load(tmp_path):
    cs = coststore.CostStore()
    cs.record("eq", "columnar", "p", 2, 4.0)
    cs.save(str(tmp_path / "cs.json"))
    fresh = coststore.CostStore()
    assert fresh.load(str(tmp_path / "cs.json")) == 1
    (ent,) = fresh.summary()
    assert 0.0 <= ent["ageS"] < 60.0
    assert ent["fastEwmaUs"] == pytest.approx(4.0)
    # v1 files (no age) load as maximally stale, never crash
    import json
    p = tmp_path / "v1.json"
    from dgraph_tpu.utils.coststore import N_BUCKETS
    p.write_text(json.dumps({"version": 1, "entries": [
        {"stage": "eq", "tier": "host", "skeleton": "", "bucket": 0,
         "hist": [0] * (N_BUCKETS + 1), "count": 1, "sum_us": 1.0,
         "ewma_us": 1.0, "max_us": 1.0}]}))
    v1 = coststore.CostStore()
    assert v1.load(str(p)) == 1
    (ent,) = v1.summary()
    assert ent["fastEwmaUs"] == pytest.approx(1.0)
