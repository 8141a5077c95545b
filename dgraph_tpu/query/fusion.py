"""Whole-plan device fusion: one executable per (skeleton, shapes, mesh).

The plan cache (query/plan.py) compiles per-stage kernels via
`jit_stage`, but a block's pipeline still hopped host<->device per
stage: filter set algebra, then the multisort, then the page slice —
each its own dispatch, each paying the fixed dispatch cost and the
host-side interpreter glue between them. This module lowers a compiled
skeleton's whole post-probe chain

    root candidates -> filter set algebra -> multi-key order
                    -> after/offset/first page

into ONE jitted executable (ops/graph.fused_rank_page), keyed
through the sanctioned `jit_stage` seam by the block's STATIC shape —
filter combinator + leaf negations, order key count + directions, page
window — plus the engine's mesh layout. Literal values (eq arguments,
cursors, offsets) are runtime operands: a param-only change re-binds
and re-dispatches with ZERO recompiles (tools/fusion_smoke.py and
tests/test_fusion.py assert the executable count stays flat).

Index probes stay on host BY DESIGN: a token probe is a memoized dict
lookup (microseconds, value-dependent), and routing it through the
planner keeps the tier machinery — compressed block-skip vs CSR vs
postings — live under fusion. What fusion removes is everything
DOWNSTREAM of the probes: the per-stage set-algebra dispatches, the
separate sort dispatch, the pagination round-trip, and the host glue
between them.

Sharding is declared, not hand-placed: FUSION_RULES is an ordered
(regex, PartitionSpec) table resolved per operand name via
parallel/mesh.match_partition_rules (the pjit partition-rule pattern).
On a mesh-less engine the rules are inert; on a mesh the executable
pins every uid-vector operand before tracing the kernel.

Filter leaves lower in one of two forms:

  RANK leaves — eq/ineq over non-list, non-lang predicates whose sort
    key is injective (int / float / bool / datetime): the leaf becomes
    a [lo, hi) range test over the predicate's DeviceValues rank
    column, computed host-side from two binary searches of the view's
    sorted distinct keys. No index probe, no per-query upload, and the
    bounds are TRACED operands — a threshold change re-binds scalars.
  SET leaves — everything else the parity theorem covers (string eq,
    has, lang/list predicates): host root-context evaluation uploads a
    sorted uid vector and the kernel applies a membership mask.

ELIGIBILITY is two-layered, and the staged path is the permanent
byte-parity oracle (tests/test_columnar_parity.py runs the fused arm
against it across clean / dirty-overlay / rollup-boundary states):

  structural (recomputed per request — the verdict carries the
    request's literal-bearing filter Functions, so it must never be
    cached on the literal-blind shared plan):
    plain block (no shortest/recurse/groupby/similar_to), a non-empty
    order of plain sortable predicates, a bounded `first`, and a
    filter that is absent or a flat AND/OR of (optionally NOT-wrapped)
    eq / has / inequality leaves over indexed predicates — exactly
    the leaf set whose root-context evaluation is proven pointwise
    (C intersect f(None) == f(C)), so leaf probes run once with no
    candidate set and the fused kernel applies them as membership
    masks (rank leaves skip even that probe).
  runtime (per request, silent fallback to staged):
    device views resident for every order key and every rank leaf
    (clean tablets — a dirty overlay falls back, the same MVCC rule
    as every device tier; a missing leaf view demotes that leaf to
    set form), 32-bit uid space, no after-cursor, page bounds within
    the kernel's selection cap, a root at least `db.fused_min_rows`
    wide, and a boundary tie mass within FUSED_SEL_CAP (the kernel
    reports overflow and the executor re-runs the staged chain).
"""

from __future__ import annotations

from typing import Optional

from jax.sharding import PartitionSpec

from dgraph_tpu.gql.ast import FilterTree, Function, GraphQuery
from dgraph_tpu.query.plan import jit_stage

# filter leaves whose root-context evaluation is pointwise-equal to
# their candidate-context evaluation (the fusion parity precondition)
_LEAF_FNS = frozenset(("eq", "has", "le", "lt", "ge", "gt", "between"))
_INEQ_FNS = frozenset(("le", "lt", "ge", "gt", "between"))

# sortable index types (mirrors executor._has_sortable_index: root
# inequalities demand one, and fused leaf probes run in root context)
_SORTABLE = frozenset(("exact", "int", "float", "datetime"))

# value types whose models.types.sort_key is INJECTIVE: equal keys
# imply equal values, so a [lo, hi) rank range over the DeviceValues
# view is byte-equal to the staged eq/ineq set. Strings are excluded —
# their key is an 8-byte prefix and ties are broken host-side.
_RANK_EXACT_TYPES = frozenset(("INT", "FLOAT", "BOOL", "DATETIME"))

# the per-plan sharding declaration (pjit partition-rule pattern,
# SNIPPETS.md): uid-vector operands ride the mesh's `uid` axis, rank
# columns follow their aligned uid vectors, scalars replicate. On a
# 1-chip mesh (or none) every rule degrades to replication.
FUSION_RULES = (
    (r"^cand$", PartitionSpec("uid")),
    (r"^fpart\d+$", PartitionSpec("uid")),
    (r"^rk_(uids|ranks)\d+$", PartitionSpec("uid")),
    (r"^dv_(uids|ranks)\d+$", PartitionSpec("uid")),
)


def _has_sortable_index(ps) -> bool:
    toks = getattr(ps, "tokenizers", ()) or ()
    return any(t in _SORTABLE for t in toks)


def _leaf_ok(fn: Optional[Function], schema) -> Optional[str]:
    """None when `fn` may serve as a fused filter leaf, else the
    reason it can't (attribution string)."""
    if fn is None:
        return "leaf:empty"
    if fn.name not in _LEAF_FNS:
        return f"leaf:{fn.name}"
    if fn.is_count or fn.needs_var or fn.is_value_var or fn.is_len_var:
        return "leaf:var-or-count"
    if not fn.attr or fn.attr == "uid":
        return "leaf:attr"
    if fn.name == "has":
        return None  # key-set membership: no index involved
    ps = schema.get(fn.attr.lstrip("~"))
    if ps is None or not getattr(ps, "indexed", False):
        # root-context evaluation of an unindexed eq/ineq raises;
        # the staged filter path legally scans instead
        return "leaf:not-indexed"
    if fn.name in _INEQ_FNS and not _has_sortable_index(ps):
        return "leaf:not-sortable"
    return None


def leaf_kind(fn: Function, schema) -> str:
    """"rank" when the leaf can evaluate as a traced rank-range test
    over the predicate's DeviceValues view with byte-exact staged
    semantics, else "set" (host eval + membership upload). Structural:
    schema + call shape only."""
    if fn.name == "has" or fn.lang:
        return "set"
    want = 2 if fn.name == "between" else 1
    if len(fn.args) != want:
        return "set"  # eq(p, [a, b]) list form: multiple token probes
    ps = schema.get(fn.attr.lstrip("~"))
    if ps is None or getattr(ps, "list_", False) \
            or getattr(ps, "lang", False):
        return "set"
    vt = getattr(ps, "value_type", None)
    if vt is None or vt.name not in _RANK_EXACT_TYPES:
        return "set"
    return "rank"


def filter_spec(ft: Optional[FilterTree], schema):
    """(fop, leaves) for a fusable filter tree, or a reason string.

    Accepted shapes: no filter; a single leaf; NOT(leaf); one flat
    AND/OR whose children are leaves or NOT(leaf). `leaves` is a list
    of (Function, negated, kind) in tree order, kind from leaf_kind."""
    if ft is None:
        return "none", []
    if ft.func is not None:
        why = _leaf_ok(ft.func, schema)
        return ("and", [(ft.func, False, leaf_kind(ft.func, schema))]) \
            if why is None else why
    if ft.op == "not" and len(ft.children) == 1 \
            and ft.children[0].func is not None:
        fn = ft.children[0].func
        why = _leaf_ok(fn, schema)
        return ("and", [(fn, True, leaf_kind(fn, schema))]) \
            if why is None else why
    if ft.op not in ("and", "or"):
        return f"filter:{ft.op}"
    leaves = []
    for c in ft.children:
        if c.func is not None:
            fn, neg = c.func, False
        elif c.op == "not" and len(c.children) == 1 \
                and c.children[0].func is not None:
            fn, neg = c.children[0].func, True
        else:
            return "filter:nested"
        why = _leaf_ok(fn, schema)
        if why is not None:
            return why
        leaves.append((fn, neg, leaf_kind(fn, schema)))
    if not leaves:
        return "filter:empty"
    return ft.op, leaves


def block_eligible(gq: GraphQuery, schema):
    """Structural fusion verdict for one block: ("ok", (fop, leaves))
    or ("<reason>", None). Cheap enough to run per request — and it
    MUST: `leaves` holds this request's Function objects (literals
    included), which a plan-scoped cache would freeze at their
    first-request values (tools/fusion_smoke.py case 2)."""
    if gq.attr == "shortest":
        return "shortest", None
    if gq.recurse is not None:
        return "recurse", None
    if gq.is_groupby:
        return "groupby", None
    if not gq.order:
        return "no-order", None
    if gq.first is None:
        return "no-first", None
    fn = gq.func
    if fn is not None and fn.name == "similar_to":
        return "similar-root", None
    for o in gq.order:
        if o.attr == "uid" or o.attr.startswith(("val(", "facet:")):
            return "order-attr", None
        if o.lang in (".", "*"):
            return "order-lang", None
        ops = schema.get(o.attr.lstrip("~"))
        if ops is None:
            return "order-unknown", None  # staged raises the GQLError
        if getattr(ops, "list_", False):
            return "order-list", None
        if getattr(ops, "value_type", None) is not None \
                and ops.value_type.name == "BOOL":
            return "order-bool", None
    spec = filter_spec(gq.filter, schema)
    if isinstance(spec, str):
        return spec, None
    return "ok", spec


def fused_executable(mesh, mesh_key, fop: str, rank_negs: tuple,
                     set_negs: tuple, set_aligned: bool, descs: tuple,
                     window: int, shift: int, rank_luts: tuple,
                     ord_luts: tuple):
    """The ONE jitted whole-block executable for this static shape,
    served from the process-wide `jit_stage` registry — the sanctioned
    dynamic-jit seam (dglint DG02 checks this file compiles through
    it and nowhere else). jax's trace cache keys on operand shapes
    below this; callers bucket every vector to powers of two
    (ops/uidvec.pad_to), so executables stay bounded per (fop, leaf
    negations, descs, window, bucket shift, view forms, shape-bucket,
    mesh layout). Rank bounds, the desc recenter and the page offset
    are traced operands: parameter changes NEVER recompile.

    `rank_luts`/`ord_luts` are the STATIC dv_view form flags (True =
    dense rank LUT, False = sorted uid/rank planes): they change which
    gather the trace emits, so they key the registry. LUT payloads are
    uid-indexed (not uid-partitioned) and replicate across the mesh;
    search payloads shard on the uid axis via FUSION_RULES."""
    import jax

    from dgraph_tpu.parallel.mesh import shard_by_rules

    def build():
        from dgraph_tpu.ops.graph import fused_rank_page

        def run(cand, rank_views, rank_los, rank_his, fparts,
                ord_views, base0, offset):
            if mesh is not None:
                def _names(prefix, views, luts):
                    out = {}
                    for i, ((a, b), is_lut) in enumerate(
                            zip(views, luts)):
                        if is_lut:  # replicated: no rule matches
                            out[f"{prefix}_lut{i}"] = a
                            out[f"{prefix}_base{i}"] = b
                        else:
                            out[f"{prefix}_uids{i}"] = a
                            out[f"{prefix}_ranks{i}"] = b
                    return out

                def _views(named, prefix, luts):
                    return tuple(
                        (named[f"{prefix}_lut{i}"],
                         named[f"{prefix}_base{i}"]) if is_lut else
                        (named[f"{prefix}_uids{i}"],
                         named[f"{prefix}_ranks{i}"])
                        for i, is_lut in enumerate(luts))

                named = {"cand": cand}
                named.update(_names("rk", rank_views, rank_luts))
                named.update(_names("dv", ord_views, ord_luts))
                named.update(
                    {f"fpart{i}": p for i, p in enumerate(fparts)})
                named = shard_by_rules(mesh, FUSION_RULES, named)
                cand = named["cand"]
                rank_views = _views(named, "rk", rank_luts)
                ord_views = _views(named, "dv", ord_luts)
                fparts = tuple(named[f"fpart{i}"]
                               for i in range(len(fparts)))
            return fused_rank_page(
                cand, rank_views, rank_luts, rank_los, rank_his,
                rank_negs, fparts, set_negs, set_aligned, fop,
                ord_views, ord_luts, descs, base0, shift, window,
                offset)

        # the program's name in a device profile and in `device.call`
        # spans: what it is and its leaf counts, not `jit_run`
        run.__name__ = run.__qualname__ = (
            f"fused_page_{fop}_r{len(rank_negs)}s{len(set_negs)}"
            f"o{len(descs)}")
        return jax.jit(run)

    return jit_stage("fusion.block_page", build,
                     static=(fop, rank_negs, set_negs, set_aligned,
                             descs, window, shift, rank_luts, ord_luts,
                             mesh_key))


def collect_preds(parsed) -> list[str]:
    """Every predicate a parsed query MAY touch (root functions,
    filters, order keys, child expansion, recurse/groupby) — the
    prefetch working set the executor hands engine/prefetch.py before
    block execution, so store-backed tablets decode while earlier
    blocks compute."""
    preds: list[str] = []
    seen: set[str] = set()

    def _add(attr: Optional[str]):
        if not attr:
            return
        p = attr.lstrip("~")
        if p and p != "uid" and not p.startswith(("val(", "facet:")) \
                and p not in seen:
            seen.add(p)
            preds.append(p)

    def _fn(fn: Optional[Function]):
        if fn is not None:
            _add(fn.attr)

    def _ft(ft: Optional[FilterTree]):
        if ft is None:
            return
        _fn(ft.func)
        for c in ft.children:
            _ft(c)

    def _gq(gq: GraphQuery):
        _add(gq.attr if gq.attr not in ("shortest",) else None)
        _fn(gq.func)
        _ft(gq.filter)
        for o in gq.order:
            _add(o.attr)
        for g in gq.groupby:
            _add(g.attr)
        if gq.shortest is not None:
            _fn(gq.shortest.from_)
            _fn(gq.shortest.to)
        for c in gq.children:
            _gq(c)

    for gq in getattr(parsed, "queries", ()):
        _gq(gq)
    return preds
