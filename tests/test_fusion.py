"""Whole-plan device fusion: the fused block executable vs the staged
chain.

The contract under test (query/fusion.py + ops/graph.fused_rank_page):

  * BYTE-PARITY — any block the fused tier serves must return exactly
    the uids, in exactly the order, the staged chain (and therefore
    the postings oracle) returns, across and/or/not filter algebra,
    rank and set leaf forms, asc/desc multi-key orders, missing-value
    sinking, offset pages and tie-heavy orders;
  * HONEST FALLBACK — every ineligible shape stamps a
    "staged:<reason>" attribution on EXPLAIN and takes the staged
    chain (never a wrong fused answer);
  * RETRACE BOUND — parameter-only changes (literals, thresholds,
    offsets) re-bind traced operands on the SAME executable:
    jit_stage_stats()["executables"] stays flat.
"""

import random
from collections import OrderedDict

import jax
import jax.numpy as jnp
import pytest

from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.ops import graph, uidvec
from dgraph_tpu.query import plan
from dgraph_tpu.query.plan import jit_stage_stats
from dgraph_tpu.utils import metrics

SEED = 20260807

SCHEMA = """
score: int @index(int) .
heat: float @index(float) .
tier: string @index(exact) .
flag: bool @index(bool) .
name: string @index(exact) .
one: int @index(int) .
"""

N = 4700
TIERS = ["gold", "silver", "bronze", "iron"]


def _quads(rng: random.Random):
    quads = []
    for i in range(1, N + 1):
        u = f"<0x{i:x}>"
        if i % 13:  # some uids miss score: the missing-sinks-last rule
            quads.append(f'{u} <score> "{rng.randint(0, 499)}" .')
        quads.append(f'{u} <tier> "{TIERS[i % 4]}" .')
        if i % 3:
            quads.append(f'{u} <heat> "{rng.randint(0, 999) / 10}" .')
        if i % 2:
            quads.append(f'{u} <flag> "{"true" if i % 4 else "false"}" .')
        quads.append(f'{u} <name> "n{i % 7}" .')
        quads.append(f'{u} <one> "7" .')  # all-ties order column
    return quads


def _build(**kw):
    db = GraphDB(device_min_edges=8, fused_min_rows=8, **kw)
    db.alter(schema_text=SCHEMA)
    db.mutate(set_nquads="\n".join(_quads(random.Random(SEED))))
    db.rollup_all()
    return db


@pytest.fixture(scope="module")
def db():
    return _build()


QUERIES = [
    # rank leaves over every rank-exact type, and/or/not algebra
    '{ q(func: eq(tier, "gold"), orderdesc: score, first: 12)'
    ' @filter(ge(score, 100)) { uid } }',
    '{ q(func: eq(tier, "silver"), orderasc: score, first: 9, offset: 30)'
    ' @filter(lt(score, 400) AND ge(heat, 5.0)) { uid } }',
    '{ q(func: eq(tier, "bronze"), orderdesc: score, first: 15)'
    ' @filter(between(score, 50, 450) OR eq(flag, true)) { uid } }',
    '{ q(func: eq(tier, "iron"), orderasc: score, first: 20)'
    ' @filter(NOT le(score, 250)) { uid } }',
    # set leaf (string eq: lossy sort key, demoted from rank form)
    '{ q(func: eq(tier, "gold"), orderdesc: score, first: 10)'
    ' @filter(eq(name, "n3") AND gt(score, 20)) { uid } }',
    # multi-key order, mixed directions, page into the missing tail
    '{ q(func: eq(tier, "silver"), orderasc: score, orderdesc: heat,'
    ' first: 25, offset: 600) { uid } }',
    # no filter at all: pure order + page fusion
    '{ q(func: eq(tier, "bronze"), orderdesc: score, first: 7) { uid } }',
]


def _uids(db, q, fused: bool):
    db.prefer_fused = fused
    try:
        return [r["uid"] for r in db.query(q)["data"]["q"]]
    finally:
        db.prefer_fused = True


def _fusion_tag(db, q):
    ex = db.query(q, explain="plan")
    return ex["extensions"]["explain"]["blocks"][0].get("fusion")


def test_fused_pages_match_staged_byte_for_byte(db):
    before = metrics.counters_snapshot()
    for q in QUERIES:
        assert _uids(db, q, fused=True) == _uids(db, q, fused=False), q
        assert _fusion_tag(db, q) == "fused", q
    delta = metrics.counters_delta(before)
    assert delta.get("query_fused_dispatch_total", 0) >= len(QUERIES)


def test_explain_reports_fused_tier(db):
    ex = db.query(QUERIES[0], explain="plan")["extensions"]["explain"]
    assert ex["tiers"]["fused"] is True
    assert ex["tiers"]["fusedMinRows"] == 8


def test_fallback_reasons_are_stamped(db):
    base = ('{ q(func: eq(tier, "gold"), orderdesc: score%s) '
            '{ uid } }')
    cases = [
        # no pagination: nothing to bound the selection with
        (base % "", "staged:no-window"),
        # a cursor uid's depth in the ordering is unprovable on device
        (base % ', first: 5, after: 0x10', "staged:after-cursor"),
        # page escapes the static survivor cap
        (base % ', first: 10, offset: 4090', "staged:deep-offset"),
    ]
    for q, want in cases:
        tag = _fusion_tag(db, q)
        assert tag is None or tag.startswith("staged:"), (q, tag)
        if tag is not None and want != "staged:no-window":
            assert tag == want, q
        # and the answer is still the staged answer
        assert _uids(db, q, fused=True) == _uids(db, q, fused=False), q
    db.prefer_fused = False
    try:
        assert _fusion_tag(db, base % ", first: 5") == "staged:disabled"
    finally:
        db.prefer_fused = True


def test_tie_overflow_falls_back(db):
    """A primary order with ONE distinct value over more candidates
    than FUSED_SEL_CAP puts the whole root in the boundary bucket:
    the kernel reports sel_count > cap and the executor must re-run
    the staged chain, byte-equal."""
    q = '{ q(func: has(one), orderasc: one, first: 5) { uid } }'
    assert _uids(db, q, fused=True) == _uids(db, q, fused=False)
    tag = _fusion_tag(db, q)
    assert tag == "staged:tie-overflow", tag


def test_param_only_change_is_zero_recompile(db):
    """Literals, thresholds and offsets are traced operands: replaying
    a warmed skeleton with different parameters must not mint new
    executables."""
    shape = ('{ q(func: eq(tier, "%s"), orderdesc: score, first: 12,'
             ' offset: %d) @filter(ge(score, %d)) { uid } }')
    db.query(shape % ("gold", 0, 100))   # warm the executable
    db.query(shape % ("gold", 4, 100))
    before = jit_stage_stats()["executables"]
    for tier, off, lo in (("silver", 0, 7), ("bronze", 9, 444),
                          ("gold", 17, 0), ("iron", 2, 250)):
        q = shape % (tier, off, lo)
        # parity per variant: a literal frozen into shared plan state
        # (instead of re-bound per request) shows up exactly here
        assert _uids(db, q, fused=True) == _uids(db, q, fused=False), q
        assert _fusion_tag(db, q) == "fused"
    assert jit_stage_stats()["executables"] == before


def test_dirty_overlay_falls_back_and_stays_correct(db):
    """A live delta overlay invalidates device views: the fused tier
    must step aside (staged attribution) yet answers stay identical;
    after rollup it re-engages."""
    q = ('{ q(func: eq(tier, "gold"), orderdesc: score, first: 12)'
         ' @filter(ge(score, 100)) { uid } }')
    db.rollup_in_read = False
    try:
        db.mutate(set_nquads='<0x7> <score> "499" .\n'
                             '<0x7> <tier> "gold" .')
        assert _uids(db, q, fused=True) == _uids(db, q, fused=False)
        db.rollup_all()
        assert _uids(db, q, fused=True) == _uids(db, q, fused=False)
        assert _fusion_tag(db, q) == "fused"
        assert "0x7" in _uids(db, q, fused=True)
    finally:
        db.rollup_in_read = True


# -- the rank lookups of the fused kernel, pinned at the trace level ----


def _sort_eqns(jaxpr):
    """Every `sort` equation of a jaxpr, nested jaxprs (pjit, while,
    cond bodies) included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_sort_eqns(sub))
    return out


def _fused_jaxpr(monkeypatch, n_cand, views, luts, descs, fop):
    """jaxpr of fused_rank_page over abstract operands, traced as the
    chip traces it (comparator sorts are the fast lookup there)."""
    monkeypatch.setattr(uidvec, "_sort_backend", lambda: True)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    # fop "and" folds one cand-aligned set leaf: a pure vector operand
    fparts = (jax.ShapeDtypeStruct((n_cand,), jnp.bool_),) \
        if fop == "and" else ()

    def run(cand, fparts, ord_views, base0, offset):
        return graph.fused_rank_page(
            cand, (), (), (), (), (), fparts, (False,) * len(fparts),
            True, fop, ord_views, luts, descs, base0, 2, 8, offset)

    return jax.make_jaxpr(run)(
        jax.ShapeDtypeStruct((n_cand,), jnp.uint32), fparts, views,
        i32, i32).jaxpr


def _search_view(n_t):
    return (jax.ShapeDtypeStruct((n_t,), jnp.uint32),
            jax.ShapeDtypeStruct((n_t,), jnp.int32))


def _lut_view(n):
    return (jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.uint32))


@pytest.mark.parametrize("fop", ["none", "and"])
@pytest.mark.parametrize("desc", [False, True])
def test_primary_rank_is_looked_up_once(monkeypatch, desc, fop):
    """One search-form order key: the full-width lookup co-sorts
    candidates with the table (two sorts), the survivors INHERIT that
    column, and the exact survivor sort is the third and last. A
    second lookup of the primary rank would make it five."""
    jaxpr = _fused_jaxpr(monkeypatch, 8192, (_search_view(16384),),
                         (False,), (desc,), fop)
    assert len(_sort_eqns(jaxpr)) == 3


@pytest.mark.parametrize("fop", ["none", "and"])
@pytest.mark.parametrize("desc", [False, True])
def test_survivor_lookup_never_cosorts_a_big_table(monkeypatch, desc,
                                                   fop):
    """LUT primary, search-form secondary over a 524,288-row table:
    the only lookup left is FUSED_SEL_CAP survivors against that
    table, which must binary-search it — no sort in the program is
    wider than the survivor vector."""
    jaxpr = _fused_jaxpr(
        monkeypatch, 65536, (_lut_view(262144), _search_view(524288)),
        (True, False), (desc, not desc), fop)
    sorts = _sort_eqns(jaxpr)
    assert sorts, "the exact survivor sort is always there"
    for eqn in sorts:
        assert max(v.aval.shape[0] for v in eqn.invars) \
            <= graph.FUSED_SEL_CAP


# -- both lookup lowerings, byte for byte against the staged chain ------

# uids 131 apart: a predicate on every node spans 1.23M uids, past the
# dense-LUT budget (search form, table padded to 16,384 rows); `dense`
# stops at node 7,900 so its span stays inside it (LUT form)
W_N = 9400
W_STRIDE = 131
W_LUT_NODES = 7900

W_SCHEMA = """
k1: string @index(exact) .
k4: string @index(exact) .
k8: string @index(exact) .
sname: int @index(int) .
heat: float @index(float) .
dense: int @index(int) .
few: int @index(int) .
"""


def _wide_quads(rng: random.Random):
    quads = []
    for i in range(W_N):
        u = f"<0x{16 + i * W_STRIDE:x}>"
        if i < 8192:
            quads.append(f'{u} <k8> "y" .')      # root of 8,192 rows
            if i % 2:
                quads.append(f'{u} <k4> "y" .')  # 4,096
            if i % 8 == 3:
                quads.append(f'{u} <k1> "y" .')  # 1,024
        if i % 13:  # the rest miss the value: they must sink last
            quads.append(f'{u} <sname> "{rng.randint(0, 499)}" .')
        if i % 3:
            quads.append(f'{u} <heat> "{rng.randint(0, 999) / 10}" .')
        if i < W_LUT_NODES and i % 7:
            quads.append(f'{u} <dense> "{rng.randint(0, 2999)}" .')
        quads.append(f'{u} <few> "{rng.randint(0, 39)}" .')
    return quads


def _wide_build(**kw):
    db = GraphDB(device_min_edges=8, fused_min_rows=8, **kw)
    db.alter(schema_text=W_SCHEMA)
    db.mutate(set_nquads="\n".join(_wide_quads(random.Random(SEED))))
    db.rollup_all()
    return db


@pytest.fixture(scope="module")
def wide_db():
    return _wide_build()


def _wq(root, order, page, flt=""):
    return (f'{{ q(func: eq({root}, "y"), {order}, {page}){flt}'
            ' { uid } }')


WIDE_QUERIES = {
    "search-asc-8k": _wq("k8", "orderasc: sname", "first: 10"),
    "search-desc-4k-offset": _wq(
        "k4", "orderdesc: sname", "first: 7, offset: 25"),
    "search-asc-1k": _wq("k1", "orderasc: heat", "first: 20, offset: 3"),
    "lut-asc-1k": _wq("k1", "orderasc: dense", "first: 12"),
    "lut-desc-8k-offset": _wq(
        "k8", "orderdesc: dense", "first: 9, offset: 40"),
    # 3,781 of k4's 4,096 rows carry sname: the page crosses into the
    # rows that miss it, which sink last under asc AND desc
    "missing-tail-asc": _wq(
        "k4", "orderasc: sname", "first: 20, offset: 3770"),
    "missing-tail-desc": _wq(
        "k4", "orderdesc: sname", "first: 20, offset: 3770"),
    # 40 distinct values over 8,192 rows: ~205 ties a bucket, the page
    # is cut inside the boundary bucket and uid breaks the ties
    "boundary-ties": _wq("k8", "orderasc: few", "first: 15, offset: 200"),
    "two-keys-search-search": _wq(
        "k8", "orderasc: few, orderdesc: heat", "first: 25, offset: 100"),
    "two-keys-lut-search": _wq(
        "k4", "orderdesc: dense, orderasc: sname", "first: 16"),
    "two-keys-search-lut": _wq(
        "k1", "orderasc: few, orderasc: dense", "first: 30, offset: 7"),
    "rank-leaf-and": _wq("k8", "orderasc: heat", "first: 12, offset: 5",
                         " @filter(ge(sname, 100) AND lt(few, 30))"),
}


def test_wide_views_take_both_forms(wide_db):
    """The parity cases below mean what their names say only if
    `dense` is served as a LUT and the others by search."""
    from dgraph_tpu.engine.device_cache import device_values

    read_ts = wide_db.query(WIDE_QUERIES["lut-asc-1k"])[
        "extensions"]["txn"]["start_ts"]
    forms = {}
    for pred in ("sname", "heat", "few", "dense"):
        dv = device_values(wide_db, wide_db.tablets[pred], read_ts)
        forms[pred] = dv.rank_lut is not None
    assert forms == {"sname": False, "heat": False, "few": False,
                     "dense": True}


@pytest.mark.parametrize("sort_backend", [False, True],
                         ids=["scan", "cosort"])
@pytest.mark.parametrize("case", sorted(WIDE_QUERIES))
def test_fused_page_parity_under_both_lookups(wide_db, monkeypatch,
                                              case, sort_backend):
    """Fused arm == staged chain, with the lookups lowered as the CPU
    lowers them (binary search) and as the chip does (co-sort where
    lookup_idx's rule says so): fresh executables per mode, since a
    trace bakes the lowering in."""
    monkeypatch.setattr(uidvec, "_sort_backend", lambda: sort_backend)
    monkeypatch.setattr(plan, "_JIT", OrderedDict())
    q = WIDE_QUERIES[case]
    fused = _uids(wide_db, q, fused=True)
    assert fused == _uids(wide_db, q, fused=False), q
    assert fused, q
    assert _fusion_tag(wide_db, q) == "fused", q


@pytest.fixture(scope="module")
def wide_mesh_db():
    from dgraph_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(axes=("uid",))
    assert mesh.shape["uid"] >= 2
    return _wide_build(mesh=mesh)


@pytest.mark.parametrize("case", ["search-desc-4k-offset",
                                  "two-keys-lut-search"])
def test_fused_page_parity_on_a_mesh(wide_mesh_db, case):
    """On a uid-sharded mesh the survivors' primary key is a gather
    over a SHARDED full-width column with a replicated index
    (FUSION_RULES pin cand and the search planes to the `uid` axis):
    same bytes as the staged chain."""
    q = WIDE_QUERIES[case]
    fused = _uids(wide_mesh_db, q, fused=True)
    assert fused and fused == _uids(wide_mesh_db, q, fused=False), q
    assert _fusion_tag(wide_mesh_db, q) == "fused", q
