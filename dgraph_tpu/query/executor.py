"""Query executor.

Semantic port of the reference's query engine (query/query.go):
  - block scheduling with variable dataflow   (query.go:2537 ProcessQuery)
  - per-node execution                        (query.go:1902 ProcessGraph)
  - filter algebra                            (query.go:2078 and/or/not)
  - order + pagination                        (query.go:2231)
  - recurse                                   (query/recurse.go)
  - shortest paths                            (query/shortest.go)
  - aggregation/math/groupby                  (query/aggregator.go, math.go,
                                               groupby.go)

TPU-first structural change: the reference launches one goroutine per
child/filter and merges with heaps; here each traversal level is ONE
batched call — device kernels (ops/graph.py) over resident tablet tiles
when the tablet is clean, numpy overlay reads when MVCC deltas are live.
Both paths share the same set-algebra semantics and are property-tested
against each other.
"""

from __future__ import annotations

import functools
import re as _re
import time as _time
from collections.abc import Mapping
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from dgraph_tpu.gql.ast import (
    FilterTree, Function, GraphQuery, ParsedResult, UID_VAR, VALUE_VAR,
)
from dgraph_tpu.gql.lexer import GQLError
from dgraph_tpu.models.schema import PREDICATE_TYPE
from dgraph_tpu.models.tokenizer import get_tokenizer, tokens_for
from dgraph_tpu.models.types import (
    TypeID, Val, convert, sort_key, to_json_value, type_name,
)
from dgraph_tpu.cluster.coordinator import StaleSnapshot
from dgraph_tpu.ops import setops
from dgraph_tpu.query.colvar import ColVar, make_colvar
from dgraph_tpu.query.devicecall import Rendezvous, device_call
from dgraph_tpu.query.retrigram import compile_trigram_query
from dgraph_tpu.storage.tablet import Tablet
from dgraph_tpu.utils import failpoint
from dgraph_tpu.utils.keys import token_bytes
from dgraph_tpu.utils.metrics import inc_counter, set_gauge
from dgraph_tpu.utils.tracing import span as _span

_EMPTY = np.empty(0, dtype=np.uint64)
_MISS_CV = object()  # _colview memo sentinel (None is a valid verdict)

# value types the columnar JSON fast path serializes (DATETIME via its
# isoformat string); GEO/BINARY/PASSWORD keep the general emitter
_FLAT_TYPES = {TypeID.INT, TypeID.FLOAT, TypeID.BOOL, TypeID.STRING,
               TypeID.DEFAULT, TypeID.DATETIME}

# value variable a similar_to() root/filter binds its per-uid scores
# to, readable as val(similar_to_score) (see _eval_similar_to)
SIMILAR_SCORE_VAR = "similar_to_score"
# similar_to's quantized tier answers k up to this; a deeper k takes
# the exact tiers (calibration holds at k_ref=10, not at any depth)
_VEC_MAX_K = 128


def _member_of(uids: np.ndarray, sorted_set: np.ndarray) -> np.ndarray:
    """Bool mask: which of `uids` appear in the sorted-unique set
    (the hit-mask half of _col_positions)."""
    return _col_positions(sorted_set, uids)[1]


def _col_positions(srcs: np.ndarray, uids: np.ndarray):
    """Membership of `uids` in a sorted column: (pos, hit mask)."""
    n = len(srcs)
    if n and n == len(uids) and (srcs is uids or (
            srcs[0] == uids[0] and srcs[-1] == uids[-1]
            and np.array_equal(srcs, uids))):
        # a has()-root scan over the column's own domain (the q020
        # shape): identity gather, no O(n log n) searchsorted. The
        # endpoint probes reject almost every length-equal miss
        # before the full O(n) compare (array_equal does NOT
        # short-circuit)
        return np.arange(n), np.ones(n, bool)
    pos = np.searchsorted(srcs, uids)
    pos = np.clip(pos, 0, max(n - 1, 0))
    hit = (srcs[pos] == uids) if n else \
        np.zeros(len(uids), bool)
    return pos, hit


def _flat_column_vectorized(ex, ch, name: str, colview, n: int):
    """Pure-numpy column build over a clean tablet's columnar view —
    no per-row Python at all for numeric columns; strings pay one
    list-gather of pre-encoded payloads."""
    from dgraph_tpu import native as _native

    srcs, tid, data, enc = colview
    uids = ex._flat_uids
    pos, hit = _col_positions(srcs, uids)
    present = hit.astype(np.uint8)
    if tid == TypeID.INT:
        out = np.zeros(n, np.int64)
        out[hit] = data[pos[hit]]
        return (name, _native.JCOL_INT, out, None, present)
    if tid == TypeID.FLOAT:
        out = np.zeros(n, np.float64)
        out[hit] = data[pos[hit]]
        return (name, _native.JCOL_FLOAT, out, None, present)
    if tid == TypeID.BOOL:
        out = np.zeros(n, np.uint8)
        out[hit] = data[pos[hit]]
        return (name, _native.JCOL_BOOL, out, None, present)
    # strings (STRING/DEFAULT/DATETIME pre-encoded at cache build)
    sel = [enc[j] for j in pos[hit].tolist()]
    lens = np.zeros(n, np.int64)
    lens[hit] = [len(e) for e in sel]
    offs = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=offs[1:])
    blob = b"".join(sel)
    bdata = np.frombuffer(blob, np.uint8) if blob \
        else np.zeros(1, np.uint8)
    return (name, _native.JCOL_STR, bdata, offs, present)


def _flat_column(ex, ch, name: str, ulist: list, n: int):
    """Extract one scalar child's values into a typed column for
    native.json_rows. One pass selects each uid's first untagged
    posting (exactly _select_posting(ps, [])); the conversion is then
    BULK per column — mutations convert values to the schema type at
    stage time, so a typed tablet's stored tids are uniform and the
    per-cell _typed/to_json_value dispatch the dict path pays is
    skipped. Returns None when values are not uniformly one
    JSON-scalar type (mixed DEFAULT columns bail to the dict path)."""
    from dgraph_tpu import native as _native

    colview = ex._colview(ch.tablet)
    if colview is not None:
        col = _flat_column_vectorized(ex, ch, name, colview, n)
        if col is not None:
            return col
    ex._ensure_child_values(ch)
    vmap = ch.values
    present = np.zeros(n, np.uint8)
    idxs: list[int] = []
    sels: list = []
    get = vmap.get
    for i, u in enumerate(ulist):
        ps = get(u)
        if not ps:
            continue
        p0 = ps[0]
        if not p0.lang:
            present[i] = 1
            idxs.append(i)
            sels.append(p0.value)
        else:
            for p in ps[1:]:
                if not p.lang:
                    present[i] = 1
                    idxs.append(i)
                    sels.append(p.value)
                    break
    if not sels:
        return (name, _native.JCOL_INT, np.zeros(n, np.int64), None,
                present)
    tid = sels[0].tid
    if any(v.tid is not tid for v in sels):
        return None
    stype = ch.tablet.schema.value_type
    if stype != TypeID.DEFAULT and tid != stype:
        # stored tid predates a schema change: the dict path would
        # convert per cell (_typed), so the bulk path must not skip it
        return None
    if tid == TypeID.BOOL:
        data = np.zeros(n, np.uint8)
        data[idxs] = [1 if v.value else 0 for v in sels]
        return (name, _native.JCOL_BOOL, data, None, present)
    if tid == TypeID.INT:
        data = np.zeros(n, np.int64)
        try:
            data[idxs] = [v.value for v in sels]
        except (OverflowError, TypeError, ValueError):
            return None
        return (name, _native.JCOL_INT, data, None, present)
    if tid == TypeID.FLOAT:
        data = np.zeros(n, np.float64)
        try:
            data[idxs] = [v.value for v in sels]
        except (TypeError, ValueError):
            return None
        return (name, _native.JCOL_FLOAT, data, None, present)
    if tid in (TypeID.STRING, TypeID.DEFAULT, TypeID.DATETIME):
        try:
            if tid == TypeID.DATETIME:
                from dgraph_tpu.models.types import iso8601
                enc = [iso8601(v.value).encode("utf-8")
                       for v in sels]
            else:
                enc = [v.value.encode("utf-8") for v in sels]
        except (AttributeError, ValueError):
            # non-str payload in a DEFAULT column, or a lone-surrogate
            # string utf-8 refuses (UnicodeEncodeError is a
            # ValueError): keep the exact dict path
            return None
        lens = np.zeros(n, np.int64)
        lens[idxs] = [len(e) for e in enc]
        offs = np.zeros(n + 1, np.int64)
        np.cumsum(lens, out=offs[1:])
        blob = b"".join(enc)
        data = np.frombuffer(blob, np.uint8) if blob \
            else np.zeros(1, np.uint8)
        return (name, _native.JCOL_STR, data, offs, present)
    return None


def _lang_matches(posting_lang: str, query_lang: str) -> bool:
    """eq(pred@de, v) compares only the @de posting; eq(pred, v) only
    the untagged one; @. compares any (ref types/facets + worker
    valueForLang semantics: an explicit tag selects that tag, no tag
    selects the untagged value)."""
    if query_lang == ".":
        return True
    if not query_lang:
        return posting_lang == ""

    def base(t):
        return t.split("-")[0].split("_")[0].casefold()

    return bool(posting_lang) and base(posting_lang) == base(query_lang)


def _probe_langs(spec, lang: str) -> list[str]:
    """Analyzer languages to probe for an index lookup. Only fulltext is
    language-aware; `@.` (any language) probes every analyzer since the
    matching value may have been indexed under any of them."""
    if spec.name != "fulltext":
        return [""]
    if lang == ".":
        from dgraph_tpu.models.stemmer import STEMMERS
        return list(STEMMERS)
    return [lang]

_INEQ = {"le", "lt", "ge", "gt", "between"}


def _has_sortable_index(schema) -> bool:
    """Whether a root inequality can walk this predicate's index in
    value order (ref tok.Tokenizer IsSortable) — read from the
    tokenizer registry, the one place sortability is defined."""
    from dgraph_tpu.models.tokenizer import get_tokenizer

    for t in schema.tokenizers:
        try:
            if get_tokenizer(t).sortable:
                return True
        except KeyError:
            continue
    return False

# vectorized comparators for numpy count columns
_CMP_VEC = {
    "eq": lambda a, b: a == b,
    "le": lambda a, b: a <= b,
    "lt": lambda a, b: a < b,
    "ge": lambda a, b: a >= b,
    "gt": lambda a, b: a > b,
}
_TERM_FUNCS = {"anyofterms", "allofterms", "anyoftext", "alloftext"}


def _np_sorted(uids) -> np.ndarray:
    # np.unique = one C sort + adjacent-dedup; the python
    # sorted(set(...)) this replaces sat on every uid() root and var
    # union
    if isinstance(uids, np.ndarray):
        return np.unique(uids.astype(np.uint64, copy=False))
    arr = np.fromiter((int(u) for u in uids), dtype=np.uint64)
    return np.unique(arr)


def _launch_traversals(badj, riders: list):
    """A Rendezvous' `launch` for the k-hop traversal: ONE call of
    bitgraph.bfs_traverse for `riders` ([(root slots, depth)], a lane
    each), not waited for. `recurse_batch_total` counts the calls,
    `recurse_batch_lanes_total` the traversals they carried: lanes a
    call is their ratio. `recurse_sharded_total` and
    `recurse_sharded_lanes_total` count those of them that took the
    program whose adjacency is split over a mesh's chips."""
    from dgraph_tpu.ops import bitgraph
    tally, reached = bitgraph.traverse(badj, riders)
    # the one small result starts for the host as soon as the device
    # has it, not a round trip after somebody asks
    tally.copy_to_host_async()
    inc_counter("recurse_batch_total")
    inc_counter("recurse_batch_lanes_total", len(riders))
    if badj.mesh is not None:
        inc_counter("recurse_sharded_total")
        inc_counter("recurse_sharded_lanes_total", len(riders))
    return tally, reached


def _land_traversals(handle, n: int) -> list:
    """A Rendezvous' `land`: every rider's (reached count, levels
    run, the lanes' reached sets still on the device, the call's
    hub-row tiles and column levels), once the call has run. One
    small array leaves the device for all of them.
    `recurse_hub_tiles_streamed_total` counts the tiles of hub rows
    the calls' levels read and `recurse_hub_tiles_total` those they
    would have read had every level read every row: their ratio is
    the share of the rows' stream that the lanes' reached sets, and
    a first level read from the roots' columns, left standing.
    `recurse_column_levels_total` counts those first levels (0 or 1
    a call; +0 serves the series from the first call on)."""
    tally, reached = handle
    counts, levels, tiles = np.asarray(tally)
    streamed, full, columns = (int(t) for t in tiles[:3])
    inc_counter("recurse_hub_tiles_streamed_total", streamed)
    inc_counter("recurse_hub_tiles_total", full)
    inc_counter("recurse_column_levels_total", columns)
    return [(int(counts[i]), int(levels[i]), reached,
             (streamed, full, columns)) for i in range(n)]


def _launch_paths(badj, pairs: list):
    """A Rendezvous' `launch` for the one-path `shortest`: ONE call of
    bitgraph.bfs_paths for `pairs` ([(source slot, target slot,
    depth)], a lane each), not waited for. `shortest_calls_total`
    counts the calls, `shortest_riders_total` the pairs they carried:
    lanes a call is their ratio."""
    from dgraph_tpu.ops import bitgraph
    out = bitgraph.paths(badj, pairs)
    out.copy_to_host_async()
    inc_counter("shortest_calls_total")
    inc_counter("shortest_riders_total", len(pairs))
    return out


def _land_paths(handle, n: int) -> list:
    """A Rendezvous' `land`: every rider's (own row of the call's one
    small result: hops, levels, the path's slots; the call's levels
    run, hub-row tiles streamed and all told, column levels), once
    the call has run. `shortest_fetch_bytes_total` counts the bytes
    of that result, all that leaves the device for the call's riders;
    `shortest_levels_run_total` the levels the calls' loops ran (a
    call ends when its LAST lane has met its source, emptied its
    frontier or spent its depth); `shortest_rows_streamed_tiles_total`
    over `shortest_rows_tiles_total` is the share of the hub rows'
    stream the levels still read, `shortest_column_levels_total` the
    first levels read from the targets' columns (0 or 1 a call)."""
    out = np.asarray(handle)
    levels, streamed, full, columns = (int(t) for t in out[-1, :4])
    inc_counter("shortest_fetch_bytes_total", out.nbytes)
    inc_counter("shortest_levels_run_total", levels)
    inc_counter("shortest_rows_streamed_tiles_total", streamed)
    inc_counter("shortest_rows_tiles_total", full)
    inc_counter("shortest_column_levels_total", columns)
    return [(out[i], (levels, streamed, full, columns)) for i in range(n)]


def _launch_similar(block, metric: str, n_real: int, riders: list):
    """A Rendezvous' `launch` for the exact `similar_to`: ONE call of
    ops/knn's lanes program over the resident `block` for `riders`
    ([(query vector, k, mask)], a lane each; they share the block,
    hence its live rows, and the metric), not waited for.
    `rendezvous_calls_total{family="similar"}` counts the calls,
    `rendezvous_riders_total` the queries they carried."""
    from dgraph_tpu.ops import knn
    return knn.launch_lanes(block.rows, block.live, riders, metric,
                            n_real)


def _land_similar(handle, n: int) -> list:
    """A Rendezvous' `land`: every rider's (own row's indices, scores,
    whether the call fell back), once the call has run. One small
    array leaves the device for all of them.
    `similar_exact_fallback_total` counts the CALLS a live lane's
    failed proof sent to the full row."""
    from dgraph_tpu.ops import knn
    idx, scores, fell_back = knn.land_lanes(handle)
    if fell_back:
        inc_counter("similar_exact_fallback_total")
    return [(idx[i], scores[i], fell_back) for i in range(n)]


def _var_domain(vmap) -> np.ndarray:
    """The sorted uid set a value var is defined on — columnar vars
    answer from their uid array without materializing Vals."""
    if isinstance(vmap, ColVar):
        return vmap.uids
    return _np_sorted(vmap.keys())


# pairwise set algebra now lives in ops/setops (one implementation for
# the executor, the k-way folds, and the microbench); inputs are sorted
# unique uid vectors (the repo-wide invariant)
_intersect = setops.intersect_pair
_union = setops.union_pair
_difference = setops.difference


@dataclass
class ExecNode:
    """Runtime state for one query node (the reference's SubGraph,
    query/query.go:222)."""

    gq: GraphQuery
    tablet: Optional[Tablet] = None
    reverse: bool = False
    src: np.ndarray = field(default_factory=lambda: _EMPTY)
    dest: np.ndarray = field(default_factory=lambda: _EMPTY)
    values: dict[int, list] = field(default_factory=dict)  # uid->Postings
    counts: dict[int, int] = field(default_factory=dict)
    children: list["ExecNode"] = field(default_factory=list)
    # recurse support: per-level (parent -> [children]) maps, and the
    # per-level resolved child list (expand() re-resolves per level)
    recurse_levels: list[dict[int, np.ndarray]] = field(default_factory=list)
    recurse_preds: list[list] = field(default_factory=list)
    emit_order: Optional[list[int]] = None  # path-var traversal order
    path_nodes: list[list[int]] = field(default_factory=list)  # shortest
    path_weights: list[float] = field(default_factory=list)
    block_idx: int = -1  # position in parsed.queries (plan memo key)
    # compiled flat blocks defer scalar-child value gathering to the
    # emitter (the columnar JSON emitter reads the column view
    # directly); _ensure_child_values materializes on demand for
    # every other consumer
    lazy_cols: bool = False
    # columnar emission fast path: uid -> ready json value for flat
    # scalar children (populated instead of `values` when eligible)
    col_vals: Optional[dict] = None
    # EXPLAIN ANALYZE observability: resolved root-set size BEFORE
    # filter/pagination (-1 = not measured, e.g. the device
    # count-at-root fast path never materializes the set)
    root_rows: int = -1
    # a root that is only COUNTED and whose uids never left the device
    # (a bound @recurse's variable read by `count(uid)` alone): the
    # block's count, with `dest` empty; -1 = count `dest`
    root_count: int = -1
    # whole-plan fusion attribution (query/fusion.py): "fused" when
    # the block's filter+order+page chain ran as ONE device
    # executable, "staged:<reason>" when a structurally-eligible
    # block fell back at runtime, "" when fusion never applied
    fused: str = ""


class Executor:
    # dglint: guarded-by=*:single-thread (one Executor per request,
    # confined to the thread running that query; cross-request state
    # lives in GraphDB / Plan / AdaptivePlanner, never here)
    def __init__(self, db, read_ts: int, ctx=None, plan=None,
                 lat=None):
        self.db = db
        self.read_ts = read_ts
        # the request's Latency (engine/db.py): every device_call
        # below adds its phases to it; None outside a served query
        self.lat = lat
        # compiled plan (query/plan.py) for this request's skeleton,
        # or None on the interpreted path (plan cache disabled, upsert
        # queries). Carries parameter-memoized stage artifacts and the
        # skeleton identity; the AST stays the source of truth for
        # parameters, so a shared plan can never leak one request's
        # literals into another's
        self.plan = plan
        # RequestContext (utils/reqctx.py): deadline + cancellation,
        # consulted at block/level boundaries so deep traversals abort
        # mid-flight (the reference checks ctx.Err() in ProcessGraph)
        self.ctx = ctx
        self.parsed: Optional[ParsedResult] = None
        self.uid_vars: dict[str, np.ndarray] = {}
        self.value_vars: dict[str, dict[int, Val]] = {}
        self._path_var_order: dict[str, list[int]] = {}
        # uid variables whose SIZE is all the request reads of them
        # (see _run_recurse_bound): name -> count; uid_vars holds an
        # empty array under the name, so the variable is defined
        self._uid_var_counts: dict[str, int] = {}
        # score-descending uid order of the current block's similar_to
        # root, set by _eval_similar_to and consumed at pagination
        self._similar_order: Optional[list[int]] = None
        # (array, (pred, token, base_ts)) of the last root that
        # returned one clean posting whole (see _posting_of)
        self._clean_posting: Optional[tuple] = None
        # per-request column-view memo (one snapshot, one verdict)
        self._cv_memo: dict = {}
        # adaptive-planner plumbing (query/planner.py): the tier
        # decisions this request consulted (EXPLAIN surfaces them) and
        # the tier the index machinery ACTUALLY served from (a decided
        # tier can still fall back — dirty tablet, missing export —
        # and cost attribution must follow the serving tier).
        # _adaptive gates every planner touch: static engines and the
        # interpreted path pay literally nothing; _dec_memo keeps a
        # request's REPEATED stage evaluations (a filter tree probing
        # one predicate dozens of times) at one est-build + consult
        self._adaptive = plan is not None \
            and getattr(db, "planner_impl", None) is not None
        self.tier_decisions: list = []
        self._dec_memo: dict = {}
        self._served_tier: Optional[str] = None
        # per-request vector-tier decisions (one per similar_to eval):
        # which tier actually scored (host/device exact, two_stage,
        # quantized, sharded) plus the quantized budget (nprobe,
        # rerank) — EXPLAIN surfaces them as tiers.vector
        self.vector_decisions: list[dict] = []

    def _checkpoint(self, where: str):
        """Block/level boundary: the `executor.level` failpoint (chaos
        tests slow traversals down here) and the request context's
        deadline/cancellation check."""
        failpoint.fire("executor.level")
        if self.ctx is not None:
            self.ctx.check(where)

    # ------------------------------------------------------------------
    # block scheduling (ref query.go:2596 dependency loop)
    # ------------------------------------------------------------------

    def run(self, parsed: ParsedResult) -> dict[str, Any]:
        return self.emit(self.execute(parsed))

    def execute(self, parsed: ParsedResult
                ) -> list[tuple[GraphQuery, ExecNode]]:
        """Process every block (var-dependency scheduled); emission is
        a separate phase so the engine can time it (Latency.encoding_ns
        — the reference ranks ToJson a top-5 hot loop) and pick the
        columnar fast path."""
        self.parsed = parsed
        pf = getattr(self.db, "prefetcher", None)
        if pf is not None:
            # announce the request's predicate working set before the
            # first block runs: cold-store blobs decode on the
            # prefetch pool while earlier blocks compute, and
            # TabletMap.get consumes them on arrival
            from dgraph_tpu.query.fusion import collect_preds
            pf.schedule(self.db, collect_preds(parsed))
        if self.plan is None:
            self._check_similar_score_ambiguity(parsed)
        else:
            # structure-only validation: ran once at plan compile (a
            # rejected combination never produces a cached plan)
            self.plan.memo(("similar_check",),
                           lambda: self._check_similar_score_ambiguity(
                               parsed))
        blocks = list(parsed.queries)
        done: list[tuple[GraphQuery, ExecNode]] = []
        pending = list(enumerate(blocks))
        for _ in range(len(blocks) + 1):
            if not pending:
                break
            still = []
            for i, gq in pending:
                needs, own = self._block_vars_of(i, gq)
                if all(self._var_defined(n) or n in own for n in needs):
                    self._checkpoint(f"block {gq.alias or gq.attr}")
                    done.append((gq, self._run_block(gq, i)))
                else:
                    still.append((i, gq))
            if len(still) == len(pending):
                missing = sorted({n for i, gq in still
                                  for n in self._block_vars_of(i, gq)[0]
                                  if not self._var_defined(n)})
                raise GQLError(
                    f"circular or undefined variable dependency: {missing}")
            pending = still
        return done

    def _block_vars_of(self, i: int, gq: GraphQuery
                       ) -> tuple[tuple, frozenset]:
        """(consumed var names, provided var names) for block `i` —
        pure structure, so a warm plan binds it once per skeleton
        instead of re-walking the AST per request."""
        def build():
            return (tuple(vc.name for vc in self._all_needs(gq)),
                    frozenset(self._provides(gq)))
        if self.plan is not None:
            return self.plan.memo(("blockvars", i), build)
        return build()

    def _check_similar_score_ambiguity(self, parsed: ParsedResult):
        """`similar_to_score` is ONE binding per request; with several
        similar_to calls the last evaluation would clobber the others
        and any val(similar_to_score) reader would silently get the
        wrong call's scores. Reject the combination up front."""
        count = 0
        reads = False

        def walk_filter(ft):
            nonlocal count, reads
            if ft is None:
                return
            if ft.func is not None:
                if ft.func.name == "similar_to":
                    count += 1
                if any(vc.name == SIMILAR_SCORE_VAR
                       for vc in ft.func.needs_var):
                    reads = True
            for c in ft.children:
                walk_filter(c)

        def walk(gq):
            nonlocal count, reads
            if gq.func is not None:
                if gq.func.name == "similar_to":
                    count += 1
                if any(vc.name == SIMILAR_SCORE_VAR
                       for vc in gq.func.needs_var):
                    reads = True
            if any(vc.name == SIMILAR_SCORE_VAR
                   for vc in gq.needs_var):
                reads = True
            if any(o.attr == f"val({SIMILAR_SCORE_VAR})"
                   for o in gq.order):
                reads = True
            walk_filter(gq.filter)
            for c in gq.children:
                walk(c)

        for q in parsed.queries:
            walk(q)
        if count > 1 and reads:
            raise GQLError(
                f"val({SIMILAR_SCORE_VAR}) is ambiguous with "
                f"{count} similar_to calls in one request; split the "
                "query so each score reader has exactly one "
                "similar_to")

    def emit(self, done) -> dict[str, Any]:
        out: dict[str, Any] = {}
        for gq, node in done:
            if gq.alias in ("var", "shortest") and gq.attr != "shortest":
                continue
            if gq.attr == "shortest":
                paths = self._emit_paths(node)
                if paths:
                    out["_path_"] = paths
                continue
            val = self._emit_block(node)
            if gq.is_groupby and not val:
                # empty root groupby omits its block key entirely
                # (ref query0:TestGroupByRootEmpty -> data {})
                continue
            out[gq.alias] = val
        return out

    def emit_json(self, done) -> str:
        """Emit the data payload as a JSON string: flat uid+scalar
        blocks go through the native columnar row serializer
        (native.json_rows — ref query/outputnode.go fastJsonNode);
        everything else falls back to dict building + json.dumps.
        Output is byte-identical to json.dumps(self.emit(done)) with
        compact separators."""
        import json as _json

        payloads: dict[str, str] = {}
        for gq, node in done:
            if gq.alias in ("var", "shortest") and gq.attr != "shortest":
                continue
            if gq.attr == "shortest":
                payloads["_path_"] = _json.dumps(
                    self._emit_paths(node), separators=(",", ":"))
                continue
            fast = self._emit_block_flat_json(node)
            if fast is None:
                val = self._emit_block(node)
                if gq.is_groupby and not val:
                    continue  # empty root groupby omits its key
                fast = _json.dumps(val, separators=(",", ":"))
            payloads[gq.alias] = fast
        return "{" + ",".join(
            _json.dumps(k) + ":" + v for k, v in payloads.items()) + "}"

    def _emit_block_flat_json(self, node: ExecNode) -> Optional[str]:
        """Columnar fast path for the overwhelmingly common result
        shape: a uid block whose children are plain scalar predicates
        (plus optional `uid`). Returns the serialized JSON array, or
        None when any feature needs the general emitter."""
        from dgraph_tpu import native as _native

        gq = node.gq
        if not node.children or node.emit_order is not None:
            # emit_order (path vars, similar_to score order) reorders
            # rows; the columnar emitter walks dest uid-ascending
            return None

        def eligible() -> Optional[list]:
            """Spec derivation is structure+schema-pure, so a warm
            plan binds it once per (skeleton, schema epoch): either
            None (this block shape keeps the general emitter — a
            predicate created on the fly after compile re-decides at
            the next epoch-keyed plan, costing only the fast path) or
            the (child index | uid marker, name) column list."""
            if (gq.recurse is not None or gq.is_groupby or gq.normalize
                    or gq.cascade or gq.ignore_reflex):
                return None
            sp = []  # (child idx, name); idx None marks the uid col
            for ci, ch in enumerate(node.children):
                cgq = ch.gq
                name = cgq.alias or cgq.attr
                if not all(32 <= ord(c) < 127 and c not in '"\\'
                           for c in name):
                    # the native emitter writes keys verbatim; names
                    # that need escaping (quotes, non-ASCII — legal in
                    # <iri> attrs and unicode identifiers) keep the
                    # dict path
                    return None
                if cgq.attr == "uid" and not cgq.is_count:
                    sp.append((None, "uid"))
                    continue
                tab = ch.tablet
                if (tab is None or cgq.is_count or cgq.agg_func
                        or cgq.attr == "math"
                        or cgq.attr.startswith("val(")
                        or cgq.langs or cgq.facets is not None
                        or cgq.facet_var or cgq.cascade or cgq.children
                        or ch.reverse or tab.schema.list_
                        or tab.schema.value_type not in _FLAT_TYPES):
                    return None
                sp.append((ci, name))
            return sp or None

        if self.plan is not None and node.block_idx >= 0 \
                and not any(c.expand for c in gq.children):
            # expand() resolves children from DATA (the src uids'
            # types), so its child list is not skeleton-stable: those
            # blocks re-derive per request
            idx_specs = self.plan.memo(
                ("flatspec", node.block_idx), eligible)
        else:
            idx_specs = eligible()
        if idx_specs is None:
            return None
        uids = node.dest
        n = len(uids)
        specs = [(None if ci is None else node.children[ci], name)
                 for ci, name in idx_specs]
        cols = []
        self._flat_uids = uids.astype(np.uint64)
        ulist = uids.tolist()
        for ch, name in specs:
            if ch is None:
                cols.append((name, _native.JCOL_UID,
                             uids.astype(np.uint64), None, None))
                continue
            col = _flat_column(self, ch, name, ulist, n)
            if col is None:
                return None
            cols.append(col)
        out = _native.json_rows(n, cols)
        if out is None:
            return None
        inc_counter("query_flat_json_total")
        return out.decode("utf-8")

    def _all_needs(self, gq: GraphQuery):
        yield from gq.needs_var
        if gq.func:
            yield from gq.func.needs_var
        if gq.filter:
            yield from self._filter_needs(gq.filter)
        for c in gq.children:
            yield from self._all_needs(c)

    def _filter_needs(self, ft: FilterTree):
        if ft.func:
            yield from ft.func.needs_var
        for c in ft.children:
            yield from self._filter_needs(c)

    def _var_defined(self, name: str) -> bool:
        return name in self.uid_vars or name in self.value_vars

    def _provides(self, gq: GraphQuery):
        """Vars a block's own subtree binds (uid vars, value vars,
        facet vars): consumers INSIDE the block must not make the
        scheduler wait for another block to provide them (ref
        query0_test.go level-based facet var tests: `path @facets(L1
        as weight) sumw: sum(val(L1))` in one block)."""
        if gq.var:
            yield gq.var
        if (gq.func is not None and gq.func.name == "similar_to") \
                or (gq.filter is not None
                    and self._filter_has_similar(gq.filter)):
            # running the block binds the score var — consumers inside
            # the block (or later blocks, via the retry rounds) see it
            yield SIMILAR_SCORE_VAR
        for varname in gq.facet_var.values():
            yield varname
        for c in gq.children:
            yield from self._provides(c)

    def _filter_has_similar(self, ft: FilterTree) -> bool:
        if ft.func is not None and ft.func.name == "similar_to":
            return True
        return any(self._filter_has_similar(c) for c in ft.children)

    # ------------------------------------------------------------------
    # one block
    # ------------------------------------------------------------------

    def _run_block(self, gq: GraphQuery, i: int = -1) -> ExecNode:
        with _span("block", alias=gq.alias or gq.attr):
            return self._run_block_inner(gq, i)

    def _run_block_inner(self, gq: GraphQuery, i: int = -1) -> ExecNode:
        self._block_root = gq
        self._block_vars = self._block_vars_of(i, gq)[1] \
            if self.plan is not None and i >= 0 \
            else set(self._provides(gq))
        # var-only blocks never reach emission, so their scalar
        # children may bind vars columnar-fast and skip posting walks
        self._block_emits = gq.alias != "var"
        node = ExecNode(gq, block_idx=i)
        if gq.attr == "shortest":
            self._run_shortest(node)
            return node
        self._similar_order = None
        if self._uid_var_counts and gq.func is not None \
                and gq.func.name == "uid" \
                and len(gq.func.needs_var) == 1 \
                and gq.func.needs_var[0].name in self._uid_var_counts:
            # the one shape _count_only_readers admits: the block is
            # `count(uid)` over the variable, whose uids stayed on
            # the device
            node.root_count = self._uid_var_counts[
                gq.func.needs_var[0].name]
            self._expand_children(node, gq.children, _EMPTY)
            return node
        root = self._device_root_count_page(gq)
        if root is None:
            fspec = self._fused_spec(gq, i)
            root = self._root_uids(gq)
            node.root_rows = int(len(root))
            paged = self._fused_block_page(gq, fspec, root, node) \
                if fspec is not None else None
            if paged is not None:
                root = paged
            else:
                if gq.filter is not None:
                    root = self._eval_filter(gq.filter, root)
                if self._similar_order is not None and not gq.order:
                    root = self._similar_paginate(gq, root, node)
                else:
                    root = self._order_paginate(gq, root)
        if not gq.order and gq.func is not None \
                and gq.func.name == "uid" and len(gq.func.needs_var) == 1:
            ordered = self._path_var_order.get(
                gq.func.needs_var[0].name)
            if ordered:
                # PATH vars emit in traversal order (ref query3_test.go
                # TestShortestPathRev) — but only the EMISSION reorders;
                # node.dest stays uid-sorted (searchsorted invariant of
                # every columnar consumer)
                inset = set(root.tolist())
                node.emit_order = [u for u in ordered if u in inset]
        node.dest = root
        if gq.var:
            self.uid_vars[gq.var] = root
        if gq.recurse is not None:
            self._run_recurse(node)
        elif gq.is_groupby:
            self._bind_groupby_vars(gq, root)
        else:
            if self.plan is not None and i >= 0 and self.plan.memo(
                    ("flatblock", i),
                    lambda: self._flat_block_eligible(i, gq)):
                # compiled dispatch: the plan proved (per skeleton +
                # schema epoch) this block is a var-free flat scalar
                # shape, so the per-child interpreter — dependency
                # scheduling, internal/uid-edge/facet branching — is
                # skipped wholesale
                self._expand_children_flat(node, gq.children, root)
            else:
                self._expand_children(node, gq.children, root)
            if gq.cascade and self._block_vars:
                # @cascade constrains the VARS the block binds, not
                # just its output rows (ref query3:TestUseVarsCascade:
                # `@cascade { L as friend { friend } }` binds L to
                # friends that themselves have friends). Var-free
                # cascade blocks skip this — emission applies their
                # cascade.
                self._cascade_rebind_vars(node)
        return node

    def _similar_paginate(self, gq: GraphQuery, root: np.ndarray,
                          node: ExecNode) -> np.ndarray:
        """similar_to roots emit nearest-first (score-descending, ties
        by uid — the order Dgraph's similar_to returns); pagination
        windows therefore cut in SCORE space. Only the emission
        reorders — node.dest stays uid-sorted, the searchsorted
        invariant of every columnar consumer (same split as path
        vars)."""
        inset = set(root.tolist())
        ordered = [u for u in self._similar_order if u in inset]
        if gq.after:
            try:
                ordered = ordered[ordered.index(gq.after) + 1:]
            except ValueError:
                pass
        if gq.offset:
            ordered = ordered[gq.offset:]
        if gq.first is not None:
            ordered = ordered[:gq.first] if gq.first >= 0 \
                else ordered[gq.first:]
        node.emit_order = ordered
        return _np_sorted(ordered)

    def _root_uids(self, gq: GraphQuery) -> np.ndarray:
        parts: list[np.ndarray] = []
        if gq.uids:
            parts.append(_np_sorted(gq.uids))
        func_args = {vc.name for vc in gq.func.needs_var} \
            if gq.func is not None else set()
        for vc in gq.needs_var:
            if vc.typ != VALUE_VAR and vc.name in self.uid_vars:
                parts.append(self.uid_vars[vc.name])
            elif vc.name in func_args and gq.func.name == "uid" \
                    and vc.name in self.value_vars \
                    and vc.name not in self.uid_vars:
                # uid(valueVar) roots at the uids the var is defined on
                # (ref query/query.go UidsFromVar)
                parts.append(_var_domain(self.value_vars[vc.name]))
        if gq.func is not None and gq.func.name != "uid":
            parts.append(self._eval_func(gq.func, None))
        return self._union_many(parts)

    # ------------------------------------------------------------------
    # root/filter functions (ref worker/task.go:1558 parseSrcFn +
    # processTask dispatch)
    # ------------------------------------------------------------------

    def _tablet(self, attr: str) -> Optional[Tablet]:
        tab = self.db.tablets.get(attr)
        if tab is not None:
            # stats plane: hottest-tablet signal (getattr: federated
            # RemoteTablet proxies have no stats fields)
            tab.touches = getattr(tab, "touches", 0) + 1
        if tab is not None \
                and getattr(tab, "base_ts", 0) > self.read_ts:
            # commits newer than this read's ts were already folded
            # into base state — the exact snapshot no longer exists.
            # Refuse (retryable) instead of serving silently-newer
            # data: the split-bank invariant broke exactly here when a
            # pinned cross-group read raced the rollup.
            raise StaleSnapshot(
                f"read at ts {self.read_ts} is below tablet "
                f"{attr!r}'s rollup watermark {tab.base_ts}; "
                f"retry at a fresh timestamp")
        return tab

    # -- columnar scan tier plumbing -----------------------------------

    def _columnar_on(self) -> bool:
        """db.prefer_columnar=False pins reads to the exact posting
        path — the differential parity suite's oracle."""
        return getattr(self.db, "prefer_columnar", True)

    def _colview(self, tab, lang: str | None = None):
        """THE chokepoint every columnar value read goes through: the
        tablet's cached column view (None on dirty/historical/mixed
        tablets or with the tier disabled), budgeted against the tile
        LRU and counted (tier routing shows in the counters).
        Memoized per request — one snapshot, one verdict — so a block
        that reads a column at eval AND emit time resolves, budgets
        and counts it once."""
        key = (id(tab), lang)
        got = self._cv_memo.get(key, _MISS_CV)
        if got is not _MISS_CV:
            return got
        cv = self._colview_inner(tab, lang)
        self._cv_memo[key] = cv
        return cv

    def _colview_inner(self, tab, lang: str | None = None):
        if not self._columnar_on() \
                or not hasattr(tab, "value_columns"):
            return None
        cv = tab.lang_value_columns(self.read_ts, lang) if lang \
            else tab.value_columns(self.read_ts)
        if cv is None:
            inc_counter("query_postings_fallback_total")
            return None
        from dgraph_tpu.engine.device_cache import host_column_tile
        host_column_tile(
            self.db, tab,
            f"_val_cols_lang@{lang}" if lang else "_val_cols", cv)
        inc_counter("query_colvar_hits_total")
        return cv

    def _index_sets(self, tab, toks: list[bytes],
                    tier: Optional[str] = None) -> list[np.ndarray]:
        """Posting sets for a token batch: one CSR probe per token on
        clean tablets (contiguous slices of one cached buffer, no
        per-token overlay generator), the exact index_uids walk
        otherwise. `tier` is the planner's pick: "postings" pins the
        exact walk; None/"columnar"/"compressed" keep the CSR."""
        csr = tab.token_index_csr(self.read_ts) \
            if tier != "postings" and self._columnar_on() \
            and hasattr(tab, "token_index_csr") \
            else None
        if csr is None:
            self._served_tier = "postings"
            return [tab.index_uids(t, self.read_ts) for t in toks]
        from dgraph_tpu.engine.device_cache import host_column_tile
        host_column_tile(self.db, tab, "_tok_csr", csr)
        inc_counter("query_index_csr_probe_total")
        self._served_tier = "columnar"
        return [csr.probe(t) for t in toks]

    # -- compressed posting tier ---------------------------------------

    def _compressed_on(self) -> bool:
        """The compressed tier rides the columnar tier's invalidation
        contract, so prefer_columnar=False (the parity oracle) pins
        BOTH off."""
        return self._columnar_on() \
            and getattr(self.db, "prefer_compressed", True)

    def _index_packs(self, tab):
        """The tablet's compressed token-index export, budgeted in the
        tile LRU by COMPRESSED size — None on dirty/historical
        tablets, unindexed predicates, or with the tier off (callers
        fall through to the dense CSR / exact index_uids chain)."""
        if not self._compressed_on() \
                or not hasattr(tab, "token_index_packs"):
            return None
        tix = tab.token_index_packs(self.read_ts)
        if tix is None:
            inc_counter("query_compressed_fallback_total")
            return None
        from dgraph_tpu.engine.device_cache import host_column_tile
        host_column_tile(self.db, tab, "_tok_packs", tix)
        return tix

    def _pack_scratch(self):
        sc = getattr(self.db, "decode_scratch", None)
        if sc is not None:
            set_gauge("codec_scratch_bytes", sc.high_water)
        return sc

    def _pack_device(self) -> bool:
        """Whether pack algebra may batch all-bitmap blocks into one
        device word-AND dispatch (setops.bitmap_and_device)."""
        return self.db.prefer_device and (
            self.db.device_min_edges <= 1
            or self.db.device_is_accelerator())

    # -- adaptive tier routing (query/planner.py) ----------------------

    def _tier_decision(self, stage: str, pred: str, est: dict,
                       avail: tuple, rows_by_tier=None):
        """Consult the adaptive planner for this stage's tier (None on
        the static/interpreted path — callers keep the flag
        heuristics). The decision is cached on the compiled plan;
        every consult lands in tier_decisions for EXPLAIN."""
        pl = getattr(self.db, "planner_impl", None)
        if pl is None or self.plan is None or not avail:
            return None
        dec = pl.choose(self.plan, stage, pred, est, avail,
                        rows_by_tier)
        if dec is not None:
            self.tier_decisions.append(dec)
        return dec

    def _record_outcome(self, dec, actual_rows: int) -> None:
        pl = getattr(self.db, "planner_impl", None)
        if pl is not None and dec is not None:
            pl.record_outcome(dec, actual_rows)

    def _routed(self, mkey: tuple, build):
        """Three-layer decision lookup: request memo -> the plan's
        routing cache (validated against the planner's
        re-optimization generation with one dict probe) -> full
        estimate + consult. The warm steady state — the plan cache
        serving every stage's decision — costs two dict reads per
        request per stage, which is what keeps the whole planner
        under the 1%% overhead gate on real (multi-stage) queries."""
        dec = self._dec_memo.get(mkey, _MISS_CV)
        if dec is not _MISS_CV:
            return dec
        pl = self.db.planner_impl
        dec = self.plan._routing.get(mkey)
        if dec is not None and pl.version(
                dec.skeleton, dec.stage, dec.pred) == dec.version:
            pl._warm_serves += 1
            self.tier_decisions.append(dec)
        else:
            dec = build()
            if dec is not None:
                routing = self.plan._routing
                if len(routing) >= self.plan.MEMO_MAX:
                    routing.clear()  # rare: stage-key churn
                routing[mkey] = dec
        self._dec_memo[mkey] = dec
        return dec

    def _index_tiers(self, tab) -> tuple:
        """Tiers the prefer_* overrides allow for a token-index stage
        on this tablet (availability, not choice — the planner picks
        within these)."""
        avail = ["postings"]
        if self._columnar_on() and hasattr(tab, "token_index_csr"):
            avail.append("columnar")
        if self._compressed_on() and hasattr(tab, "token_index_packs"):
            avail.append("compressed")
        return tuple(avail)

    def _tabstats(self, tab) -> Optional[dict]:
        """Cached BASE tablet statistics, or None for stat-less
        proxies (same guard as explain's estimator). The per-base_ts
        aggregate is computed once per rollup and shared with
        /debug/stats; the steady-state read on this query hot path is
        one tuple compare (tabstats.tablet_base_stats) — NOT the full
        tablet_stats(), whose live residency walk costs ~10 µs per
        call."""
        if tab is None or not hasattr(tab, "base_ts"):
            return None
        from dgraph_tpu.storage.tabstats import tablet_base_stats
        return tablet_base_stats(tab)

    def _dirty_slack(self, tab) -> int:
        from dgraph_tpu.storage.tabstats import dirty_ops
        return dirty_ops(tab)

    def _posting_est(self, tab, token: bytes) -> Optional[dict]:
        """EXPLAIN-shaped row count of ONE token's posting list on a
        clean tablet: the length of the base index's own array, exact
        at any read_ts the base serves. None on dirty or historical
        reads and on proxies without an index (callers keep the
        histogram's guess)."""
        index = getattr(tab, "index", None)
        if index is None or tab.dirty() or self.read_ts < tab.base_ts:
            return None
        n = len(index.get(token, _EMPTY))
        return {"estRows": n, "estRowsMax": n, "basis": "exact",
                "source": "posting length"}

    def _token_est(self, tab, n_tokens: int) -> dict:
        """EXPLAIN-shaped row estimate for an n-token index probe:
        per-token quantile from the tabstats posting-length histogram
        (the satellite basis), capped at keys + dirty slack. The
        quantile is cached on the tablet per base_ts — this sits on
        the eq/terms hot path."""
        st = self._tabstats(tab)
        if st is None:
            return {"estRows": -1, "estRowsMax": -1,
                    "basis": "unknown"}
        cached = getattr(tab, "_tokq_cache", None)
        if cached is not None and cached[0] == tab.base_ts:
            per = cached[1]
        else:
            from dgraph_tpu.query.planner import token_quantile
            per = token_quantile(st["tokenIndex"])
            tab._tokq_cache = (tab.base_ts, per)
        cap = st["nSrc"] + self._dirty_slack(tab)
        return {"estRows": min(int(round(n_tokens * per)), cap),
                "estRowsMax": cap, "basis": "stats",
                "source": "token-length histogram"}

    def _index_union(self, tab, toks: list[bytes],
                     tier: Optional[str] = None) -> np.ndarray:
        """k-token index union, staying on compressed blocks where
        they exist: the hybrid index hands back zero-copy dense
        slices for its small-list tail and packs for the long lists
        (setops.union_mixed merges the compressed side first).
        `tier` (the planner's pick) caps the ladder: "columnar" skips
        the packs, "postings" pins the exact walk; fallbacks on
        missing exports still cascade."""
        tix = self._index_packs(tab) \
            if tier in (None, "compressed") else None
        if tix is not None:
            ops = [o for o in (tix.probe_operand(t) for t in toks)
                   if o is not None]
            inc_counter("query_compressed_setops_total")
            self._served_tier = "compressed"
            return setops.union_mixed(ops,
                                      scratch=self._pack_scratch())
        return self._union_many(self._index_sets(tab, toks, tier))

    def _index_intersect(self, tab, toks: list[bytes],
                         tier: Optional[str] = None) -> np.ndarray:
        """k-token index intersection with block-descriptor skipping:
        dense operands intersect smallest-first, the survivor vector
        probes each pack in compressed form — blocks with no key
        overlap are NEVER decoded (all-pack inputs additionally batch
        bitmap blocks into one word-AND, device-routed when worth
        it). `tier` as in _index_union."""
        tix = self._index_packs(tab) \
            if tier in (None, "compressed") else None
        if tix is not None:
            ops = []
            for t in toks:
                o = tix.probe_operand(t)
                if o is None:
                    return _EMPTY  # a missing token empties the AND
                ops.append(o)
            inc_counter("query_compressed_setops_total")
            self._served_tier = "compressed"
            return setops.intersect_mixed(
                ops, scratch=self._pack_scratch(),
                device=self._pack_device())
        return self._intersect_many(self._index_sets(tab, toks, tier))

    def _trigram_tier(self, tab, kind: str, n_tokens: int):
        """Tier decision for a trigram-index probe batch (regexp /
        match) — stage "setops" like the other token set ops,
        memoized per request."""
        if not self._adaptive:
            return None
        return self._routed(
            ("setops", tab.pred, kind, n_tokens),
            lambda: self._tier_decision(
                "setops", tab.pred, self._token_est(tab, n_tokens),
                self._index_tiers(tab)))

    def _index_count_filter(self, tab, toks: list[bytes], need: int,
                            tier: Optional[str] = None) -> np.ndarray:
        """Uids in >= need of the tokens' posting lists (the match()
        q-gram bound): candidates come from the smallest operands
        (pigeonhole), the long packed lists answer by block-skipping
        membership probes without decoding. `tier` as in
        _index_union."""
        tix = self._index_packs(tab) \
            if tier in (None, "compressed") else None
        if tix is not None:
            ops = [o for o in (tix.probe_operand(t) for t in toks)
                   if o is not None]
            inc_counter("query_compressed_setops_total")
            self._served_tier = "compressed"
            return setops.count_filter_mixed(
                ops, need, scratch=self._pack_scratch())
        buckets = [b for b in self._index_sets(tab, toks, tier)
                   if len(b)]
        if not buckets:
            return _EMPTY
        from dgraph_tpu import native as _nat
        got = _nat.merge_count(buckets, need) if _nat.available() \
            else None
        return got if got is not None \
            else setops.count_filter(buckets, need)

    # np.unique cost per element of a k-way union — the fixed side of
    # the device-tier choice is the measured dispatch RTT
    _HOST_PER_SETOP_EL = 2e-8
    _DEVICE_RATIO_SETOP = 0.9  # device sort ≈ host sort at these sizes

    def _union_many(self, parts: list[np.ndarray]) -> np.ndarray:
        """k-way union; one device co-sort dispatch when the host cost
        clears the RTT (uidvec.merge_many), else concat + one sort."""
        if len(parts) >= 4 and self.db.prefer_device:
            total = sum(len(p) for p in parts)
            if total >= (1 << 17) and self._device_worth(
                    total * self._HOST_PER_SETOP_EL,
                    device_ratio=self._DEVICE_RATIO_SETOP):
                with device_call("query_device_setops_total",
                                 sink=self.lat,
                                 program="merge_many") as dc:
                    got = setops.union_many_device(parts, sync=dc.wait)
                if got is not None:
                    return got
        return setops.union_many(parts)

    def _intersect_many(self, parts: list[np.ndarray]) -> np.ndarray:
        """k-way intersection, smallest set first. Under the adaptive
        planner the per-pair gallop-vs-merge pivot is density-derived
        (planner.gallop_ratio) instead of the fixed 16x skew."""
        if len(parts) >= 4 and self.db.prefer_device:
            total = sum(len(p) for p in parts)
            if total >= (1 << 17) and self._device_worth(
                    total * self._HOST_PER_SETOP_EL,
                    device_ratio=self._DEVICE_RATIO_SETOP):
                with device_call("query_device_setops_total",
                                 sink=self.lat,
                                 program="intersect_many") as dc:
                    got = setops.intersect_many_device(parts,
                                                       sync=dc.wait)
                if got is not None:
                    return got
        pl = getattr(self.db, "planner_impl", None)
        if pl is not None and len(parts) >= 2:
            lens = [len(p) for p in parts]
            # per-fold schedule (>=3 parts: the accumulator-density
            # model has something to decay over), else the flat
            # density-derived ratio; both only pick strategies, the
            # intersection bytes are identical
            sched = pl.intersect_schedule(lens)
            if sched is not None:
                return setops.intersect_many(parts, gallop_ratio=sched)
            return setops.intersect_many(
                parts, gallop_ratio=pl.gallop_ratio(min(lens),
                                                    max(lens)))
        return setops.intersect_many(parts)

    def _eval_func(self, fn: Function, candidates: Optional[np.ndarray]
                   ) -> np.ndarray:
        name = fn.name
        if fn.attr == "uid" and name != "uid":
            # `uid` is a result field, never a predicate argument
            # (ref query1:TestUidAttr: 'Argument cannot be "uid"')
            raise GQLError('Argument cannot be "uid"')
        if name == "uid":
            parts = [_np_sorted(fn.uids)]
            for vc in fn.needs_var:
                if vc.name in self.uid_vars:
                    parts.append(self.uid_vars[vc.name])
                elif vc.name in self.value_vars:
                    # uid(valueVar): the uids the var is defined on
                    # (ref query/query.go UidsFromVar / outputnode uses)
                    parts.append(
                        _var_domain(self.value_vars[vc.name]))
            uids = self._union_many(parts)
            return uids if candidates is None \
                else _intersect(candidates, uids)
        if name == "type":
            return self._eval_eq_tokens(
                self._tablet(PREDICATE_TYPE),
                [Val(TypeID.STRING, fn.args[0].value)], candidates)
        if name == "has":
            if fn.attr.startswith("~"):
                # has(~pred): uids with at least one INCOMING edge
                # (ref worker/task.go reverse attr handling)
                tab = self._tablet(fn.attr[1:])
                if tab is None:
                    return _EMPTY
                if not tab.schema.reverse:
                    raise GQLError(
                        f"has(~{fn.attr[1:]}) needs @reverse on "
                        f"{fn.attr[1:]!r}")
                alluids = tab.dst_uids(self.read_ts)
            else:
                tab = self._tablet(fn.attr)
                if tab is None:
                    return _EMPTY
                alluids = tab.src_uids(self.read_ts)
            return alluids if candidates is None \
                else _intersect(candidates, alluids)
        if fn.is_count:
            return self._eval_count_fn(fn, candidates)
        if fn.is_value_var or fn.is_len_var:
            return self._eval_var_fn(fn, candidates)
        if name == "eq":
            tab = self._tablet(fn.attr)
            eqps = tab.schema if tab is not None \
                else self.db.schema.get(fn.attr)
            if candidates is None and eqps is not None \
                    and not eqps.indexed:
                # root eq needs an index to look tokens up in — a
                # schema property, data or not (ref query1:
                # TestNameNotIndexed; filters compare values per
                # candidate uid and stay legal without one)
                raise GQLError(
                    f"predicate {fn.attr!r} is not indexed")
            if fn.needs_var and not fn.is_value_var:
                # eq(pred, val(v)): each uid compares against ITS OWN
                # val(v) (ref query.go valueVarAggregation semantics)
                return self._eval_eq_own_val(tab, fn, candidates)
            vals = [Val(TypeID.DEFAULT, a.value) for a in fn.args]
            return self._eval_eq_tokens(tab, vals, candidates,
                                        fn.lang or "")
        if name in _INEQ:
            return self._eval_ineq(fn, candidates)
        if name in _TERM_FUNCS:
            return self._eval_terms(fn, candidates)
        if name in ("anyof", "allof"):
            return self._eval_anyof(fn, candidates)
        if name == "regexp":
            return self._eval_regexp(fn, candidates)
        if name == "match":
            return self._eval_match(fn, candidates)
        if name == "uid_in":
            return self._eval_uid_in(fn, candidates)
        if name == "checkpwd":
            return self._eval_checkpwd(fn, candidates)
        if name in ("near", "within", "contains", "intersects"):
            return self._eval_geo(fn, candidates)
        if name == "similar_to":
            return self._eval_similar_to(fn, candidates)
        raise GQLError(f"function {name!r} not supported")

    def _eval_similar_to(self, fn: Function, candidates) -> np.ndarray:
        t0 = _time.perf_counter_ns()
        try:
            with _span("similar_to", pred=fn.attr) as sp:
                return self._eval_similar_to_inner(fn, candidates, sp)
        finally:
            # the span's own time as a counter: less
            # device_call_ns_total{family="similar"} it is what a
            # similar_to costs off the chip
            inc_counter("similar_ns_total",
                        _time.perf_counter_ns() - t0)

    def _eval_similar_to_inner(self, fn: Function, candidates,
                               sp: Optional[dict] = None) -> np.ndarray:
        """similar_to(embedding, k, $vec[, metric]): the k uids whose
        stored float32vector scores closest to the query vector
        (forward-port of modern Dgraph's similar_to onto the v1.1.x
        surface). Under `@index(vector)` every tier gives ONE answer,
        the k rows of greatest score ordered by (-score, uid):
        brute-force scoring over the predicate's columnar vector block
        (ops/knn.py, TPU-KNN formulation) on the device, where the
        two-stage top-k is proved exact in the call or answered by the
        full row (span attribute `exact_fallback`); mesh-sharded
        per-shard top-k + k-way merge above shard_min_edges; exact
        numpy otherwise. The quantized IVF tier answers only where the
        schema asks for approximation (`@index(vector(ivf))`), never
        because the predicate grew. MVCC overlay rows are scored
        host-side and merged, so reads at any ts see exactly their
        snapshot. Scores land in the `similar_to_score` value variable
        (val(similar_to_score)). The span gains `k`, `rows` (the
        block's), `candidates` (rows the mask leaves)."""
        from dgraph_tpu.models.types import parse_vector
        from dgraph_tpu.ops import knn as _knn

        tab = self._tablet(fn.attr)
        schema = tab.schema if tab is not None \
            else self.db.schema.get(fn.attr)
        if schema is None:
            raise GQLError(
                f"predicate {fn.attr!r} is not in the schema")
        if schema.value_type != TypeID.FLOAT32VECTOR:
            raise GQLError(
                f"similar_to requires a float32vector predicate; "
                f"{fn.attr!r} is {type_name(schema.value_type)}")
        if candidates is None and not (
                schema.indexed and "vector" in schema.tokenizers):
            # root similar_to needs @index(vector), a schema property
            # whether or not data exists (same contract as root eq)
            raise GQLError(
                f"predicate {fn.attr!r} needs @index(vector) for "
                "similar_to at the query root")
        if len(fn.args) < 2:
            raise GQLError(
                "similar_to(pred, k, vector) needs a k and a query "
                "vector")
        try:
            k = int(str(fn.args[0].value), 0)
        except ValueError:
            raise GQLError(
                f"similar_to k must be an integer, got "
                f"{fn.args[0].value!r}")
        if k < 1:
            raise GQLError("similar_to k must be >= 1")
        try:
            qvec = parse_vector(fn.args[1].value)
        except (ValueError, TypeError) as e:
            raise GQLError(f"bad similar_to query vector: {e}")
        metric = "cosine"
        if len(fn.args) > 2:
            metric = str(fn.args[2].value).lower()
            if metric not in _knn.METRICS:
                raise GQLError(
                    f"similar_to metric must be one of "
                    f"{'/'.join(_knn.METRICS)}, got {metric!r}")
        if tab is None:
            return _EMPTY
        if not hasattr(tab, "vector_view"):
            # federated RemoteTablet proxy: the embedding block lives
            # on another group and brute-force scoring must run where
            # the data is — keep the vector predicate co-located with
            # the querying group (clean error, not an AttributeError)
            raise GQLError(
                f"similar_to on {fn.attr!r} requires the vector "
                "predicate to be served by this group (cross-group "
                "vector search is not supported)")
        try:
            view = tab.vector_view(self.read_ts)
        except ValueError as e:
            raise GQLError(str(e))
        if view.dim and len(qvec) != view.dim:
            raise GQLError(
                f"similar_to query vector has dimension {len(qvec)}; "
                f"predicate {fn.attr!r} stores dimension {view.dim}")

        ex_uids, ex_vecs = view.extra_uids, view.extra_vecs
        if candidates is not None and len(ex_uids):
            exm = _member_of(ex_uids, candidates)
            ex_uids, ex_vecs = ex_uids[exm], ex_vecs[exm]
        parts: list = []
        n = len(view.base_uids)
        # the rows the block may answer from, found without a pass
        # over every row where the input says what they are: all of
        # them ("none": no mask), one clean posting whose mask is a
        # resident of the device ("tile_hit"; "tile_miss" stores it),
        # or a mask made for this call from the candidates ("call")
        n_pad = _knn.padded_rows(n)
        posting = self._posting_of(candidates) if view.clean else None
        tile = None
        mask = None          # host bool, over the live or padded rows
        if candidates is None and view.clean:
            n_cand, source = n, "none"
        else:
            source = "call"
            if posting is not None and self.db.prefer_device:
                from dgraph_tpu.engine.device_cache import \
                    similar_mask_tile
                tile = similar_mask_tile(self.db, tab, posting,
                                         view.base_uids)
            if tile is not None:
                n_cand = tile.n_cand
            else:
                mask = self._similar_host_mask(view, candidates, n_pad)
                n_cand = int(np.count_nonzero(mask))

        def host_mask() -> np.ndarray:
            # the tiers off the single chip take a host mask over the
            # live rows, as they always did
            nonlocal source
            if source == "none":
                return view.base_keep
            source = "call"
            m = mask if mask is not None else \
                self._similar_host_mask(view, candidates, n_pad)
            return m[:n]

        if sp is not None:
            sp.update(k=int(k), rows=int(n), candidates=n_cand,
                      exact_fallback=0)
        if n_cand:
            qm = qvec[None, :]
            # quantized eligibility: a schema that asks for it
            # (`@index(vector(ivf))`: size alone never makes an answer
            # approximate), a trained index for the CURRENT
            # base state, root context (a filter's candidate subset
            # can defeat the probe's recall budget — candidates keep
            # the exact tiers), and k within the calibrated regime.
            # vec_quantized=False is the exact-path parity oracle.
            ivf = tab.vector_ivf() \
                if hasattr(tab, "vector_ivf") else None
            quant_ok = (ivf is not None and self.db.vec_quantized
                        and schema.vector_approx
                        and candidates is None
                        and k <= _VEC_MAX_K)
            # tier arbitration: the planner weighs the measured
            # dispatch RTT / observed per-stage cost against the
            # per-tier scanned-row counts (the quantized tier scores
            # ~n*nprobe/nlist rows + the re-rank, not n); static mode
            # keeps the flag ladder. The mesh-sharded tier stays
            # first — capacity, not latency.
            dec = None
            force_device = self.db.prefer_device \
                and self.db.device_min_edges <= 1
            avail = ["postings"]
            if self.db.prefer_device and self.db.device_min_edges > 1:
                avail.append("device")
            if quant_ok:
                avail.append("quantized")
            if self._adaptive and len(avail) > 1 \
                    and not force_device and self.db.mesh is None:
                rows_by_tier = None
                if quant_ok:
                    rows_by_tier = {"quantized": ivf.scanned_rows()}
                dec = self._tier_decision(
                    "similar_to", fn.attr,
                    {"estRows": n, "estRowsMax": n, "basis": "exact",
                     "source": "vector block rows"},
                    tuple(avail), rows_by_tier)
            if dec is not None:
                use_quant = dec.tier == "quantized"
                use_device = dec.tier == "device"
            else:
                # device_min_edges <= 1 force-routes device (the
                # pinned-tier debugging convention) ahead of the tier
                use_quant = quant_ok and not force_device
                use_device = not use_quant \
                    and self.db.prefer_device \
                    and n >= self.db.device_min_edges
            vdec = {"pred": fn.attr, "k": int(k), "n": int(n),
                    "metric": metric}
            if self.db.mesh is not None \
                    and n >= self.db.shard_min_edges:
                if quant_ok:
                    idx, sc = self._sharded_ivf_topk(
                        tab, ivf, view, qm, k, metric, host_mask())
                    vdec.update(tier="sharded_quantized",
                                **self._vec_budget(ivf, k))
                else:
                    idx, sc = self._sharded_vec_topk(
                        tab, view, qm, k, metric, host_mask())
                    vdec["tier"] = "sharded"
                if sp is not None:
                    # cost attribution follows the SERVING tier: the
                    # mesh-quantized span must not pollute the exact
                    # device tier's cost cells
                    sp["tier"] = vdec["tier"] \
                        if vdec["tier"] == "sharded_quantized" \
                        else "device"
            elif use_quant:
                from dgraph_tpu.ops import ivf as _ivf
                idx, sc = _ivf.search(
                    ivf, view.base_vecs, qm, k, metric,
                    keep=host_mask())
                inc_counter("query_similar_quantized_total")
                budget = self._vec_budget(ivf, k)
                scanned = budget["scannedRows"]
                vdec.update(tier="quantized", **budget)
                if sp is not None:
                    sp["tier"] = "quantized"
                    # the span's size drives the coststore cell's
                    # bucket: record the SCANNED rows, the same size
                    # axis rows_by_tier gave the decision probe — a
                    # full-n bucket would park quantized observations
                    # where the planner never looks
                    sp["n"] = int(scanned)
            elif use_device:
                from dgraph_tpu.engine.device_cache import (
                    device_vector_block, store_similar_mask,
                )
                # a counted, evictable tile (engine/device_cache.py)
                block = device_vector_block(self.db, tab,
                                            view.base_vecs)
                dev_mask = mask
                if tile is not None:
                    dev_mask, source = tile.mask, "tile_hit"
                elif posting is not None:
                    dev_mask = store_similar_mask(
                        self.db, tab, posting, view.base_uids, mask,
                        n_cand).mask
                    source = "tile_miss"
                if mask is not None:
                    # a mask went up, with the call or as its tile
                    inc_counter("similar_masked_total")
                # the calls in flight over this block ride one call
                # of the program (devicecall.Rendezvous): this
                # request's block is its own all the same, its wait
                # the time until its call's result
                with device_call("query_device_similar_total",
                                 sink=self.lat,
                                 program=_knn.DEVICE_PROGRAM) as dc:
                    meet = Rendezvous.at(block, _knn.LANES,
                                         family="similar", key=metric)
                    ride = dc.wait_for(
                        lambda: meet.ride(
                            (qvec, k, dev_mask),
                            functools.partial(_launch_similar, block,
                                              metric, n),
                            _land_similar, self.ctx),
                        out_bytes=4 * (2 * min(k, n_pad) + 1))
                    dc.note(lanes=ride.lanes,
                            batch_wait_us=ride.waited_ns // 1000)
                    # the lane's own k of the call's largest
                    lane_idx, lane_sc, fell_back = ride.result
                    idx, sc = lane_idx[None, :k], lane_sc[None, :k]
                vdec["tier"] = "two_stage" \
                    if _knn.plan_two_stage(n, k) > 0 else "exact"
                if sp is not None:
                    sp["tier"] = "device"
                    sp["n"] = int(n)
                    sp["exact_fallback"] = int(fell_back)
            else:
                idx, sc = _knn.topk_host(view.base_vecs, qm, k,
                                         metric, mask=host_mask())
                vdec["tier"] = "exact"
                if sp is not None:
                    sp["tier"] = "postings"
                    sp["n"] = int(n)
            inc_counter("similar_mask_total", labels={"source": source})
            if sp is not None:
                sp["mask"] = source
            self.vector_decisions.append(vdec)
            self._record_outcome(dec, n)
            row, s = idx[0], sc[0]
            ok = np.isfinite(s) & (row < n) & (row >= 0)
            parts.append((view.base_uids[row[ok]], s[ok]))
        if len(ex_uids):
            idx, sc = _knn.topk_host(ex_vecs, qvec[None, :], k, metric)
            row, s = idx[0], sc[0]
            ok = np.isfinite(s)
            parts.append((ex_uids[row[ok]], s[ok]))
        uids, scores = _knn.merge_topk(parts, k)
        self.value_vars[SIMILAR_SCORE_VAR] = {
            int(u): Val(TypeID.FLOAT, float(s))
            for u, s in zip(uids.tolist(), scores.tolist())}
        if candidates is None:
            # root: the block emits nearest-first (_similar_paginate)
            self._similar_order = [int(u) for u in uids.tolist()]
        return np.sort(uids.astype(np.uint64))

    def _posting_of(self, candidates) -> Optional[tuple]:
        """(predicate, token, base_ts) if `candidates` IS one clean
        posting: the very array a one-token `eq` root returned
        (_eval_eq_tokens_inner notes it). Whatever narrows or widens a
        set makes another array, so identity is the whole test."""
        noted = self._clean_posting
        if candidates is None or noted is None \
                or noted[0] is not candidates:
            return None
        return noted[1]

    @staticmethod
    def _similar_host_mask(view, candidates, n_pad: int) -> np.ndarray:
        """The mask of one call: the candidates' rows, over the
        block's padded rows, less the rows the overlay touches; at
        the root the view's own mask over the live rows."""
        from dgraph_tpu.ops.knn import candidate_mask

        if candidates is None:
            return view.base_keep
        mask = candidate_mask(view.base_uids, candidates, n_pad)
        if not view.clean:
            mask[:len(view.base_keep)] &= view.base_keep
        return mask

    def _vec_budget(self, ivf, k: int) -> dict:
        """The quantized tier's live budget as EXPLAIN reports it —
        ONE builder so the sharded and single-device tiers.vector
        entries can't drift apart. nprobe clamps to nlist exactly
        like ops/ivf.search does."""
        from dgraph_tpu.ops import ivf as _ivf
        return {
            "nprobe": min(ivf.nlist, int(ivf.nprobe)),
            "rerank": _ivf.rerank_depth(k),
            "nlist": ivf.nlist,
            "scannedRows": ivf.scanned_rows(),
            "sampleRecall": round(float(ivf.sample_recall), 4),
        }

    def _sharded_ivf_topk(self, tab, ivf, view, qm, k, metric,
                          base_mask):
        """Quantized scoring over a sharded corpus: per-shard
        candidate top-R + k-way merge + exact re-rank
        (parallel/dist_knn.sharded_ivf_topk)."""
        from dgraph_tpu.parallel.dist_knn import sharded_ivf_topk

        inc_counter("query_similar_sharded_total")
        return sharded_ivf_topk(
            self.db.mesh, ivf, view.base_vecs, qm, k, metric,
            keep=base_mask)

    def _sharded_vec_topk(self, tab, view, qm, k, metric, base_mask):
        """Mesh-sharded scoring: the block rides the `uid` axis, each
        shard computes a local top-k, one all_gather merges
        (parallel/dist_knn.py)."""
        from dgraph_tpu.parallel.dist_knn import (
            shard_corpus, sharded_topk,
        )

        mesh = self.db.mesh
        cached = getattr(tab, "_device_vecs_sharded", None)
        if cached is not None and cached[0] == tab.base_ts:
            block, n_real = cached[1], cached[2]
        else:
            block, n_real = shard_corpus(mesh, view.base_vecs)
            tab._device_vecs_sharded = (tab.base_ts, block, n_real)
        with device_call("query_device_similar_sharded_total",
                         sink=self.lat,
                         program="sharded_topk") as dc:
            return sharded_topk(mesh, block, qm, k, metric,
                                mask=base_mask, n_real=n_real,
                                sync=dc.wait)

    def _eval_geo(self, fn: Function, candidates) -> np.ndarray:
        """near/within/contains/intersects: geo-cell index prefilter +
        exact host verify (ref types/geofilter.go:65,222 +
        worker/task.go:1330 filterGeoFunction; s2index.go covers become
        the lon/lat grid in models/geo.py)."""
        from dgraph_tpu.models import geo as G

        tab = self._tablet(fn.attr)
        if tab is None:
            return _EMPTY
        if tab.schema.value_type != TypeID.GEO:
            raise GQLError(
                f"{fn.name} requires a geo predicate, "
                f"{fn.attr!r} is {tab.schema.value_type.name.lower()}")
        try:
            qgeom, dist = self._geo_args(fn)
        except (ValueError, KeyError, IndexError, TypeError) as e:
            raise GQLError(f"bad {fn.name} argument: {e}")

        # index prefilter: cells covering the query region, coarse->fine
        if fn.name == "near":
            bbox = G.expand_bbox_m(tuple(qgeom["coordinates"]), dist)
        else:
            bbox = G._bbox(qgeom)
        spec = get_tokenizer("geo")
        indexed = tab.schema.indexed and "geo" in tab.schema.tokenizers
        if indexed:
            scan = self._index_union(
                tab, [token_bytes(spec.ident, t)
                      for t in G.query_tokens(bbox)])
            if candidates is not None:
                scan = _intersect(candidates, scan)
        elif candidates is not None:
            scan = candidates
        else:
            raise GQLError(
                f"{fn.name} requires @index(geo) on {fn.attr!r} at the "
                "query root")

        keep = []
        for u in scan.tolist():
            for p in tab.get_postings(u, self.read_ts):
                try:
                    g = G.parse_geom(self._typed(tab, p).value)
                except ValueError:
                    continue
                if self._geo_match(fn.name, g, qgeom, dist):
                    keep.append(u)
                    break
        return np.asarray(keep, dtype=np.uint64)

    @staticmethod
    def _geo_args(fn: Function):
        """Parse [lon, lat] / polygon literal (+ distance for near)."""
        import json as _json

        from dgraph_tpu.models.geo import GeoError, parse_geom
        raw = fn.args[0].value
        obj = _json.loads(raw) if isinstance(raw, str) else raw
        if isinstance(obj, list):
            if obj and isinstance(obj[0], (int, float)):
                obj = {"type": "Point", "coordinates": obj}
            elif obj and isinstance(obj[0][0], (int, float)):
                obj = {"type": "Polygon", "coordinates": [obj]}
            else:
                obj = {"type": "Polygon", "coordinates": obj}
        qgeom = parse_geom(obj)
        dist = 0.0
        if fn.name == "near":
            if len(fn.args) < 2:
                raise GeoError("near needs a distance in meters")
            dist = float(fn.args[1].value)
            if qgeom["type"] != "Point":
                raise GeoError("near expects a point")
        return qgeom, dist

    @staticmethod
    def _geo_match(name: str, g: dict, q: dict, dist: float) -> bool:
        from dgraph_tpu.models import geo as G
        if name == "near":
            return G.min_distance_m(g, tuple(q["coordinates"])) <= dist
        if name == "within":
            return G.geom_within(g, q)
        if name == "contains":
            if q["type"] == "Point":
                return G.geom_contains_point(g, tuple(q["coordinates"]))
            return G.geom_within(q, g)
        return G.geom_intersects(g, q)

    def _eval_checkpwd(self, fn: Function, candidates) -> np.ndarray:
        """UIDs whose stored password hash verifies against the given
        plaintext (ref worker/task.go handleCheckPassword +
        types/password.go VerifyPassword)."""
        from dgraph_tpu.models.types import verify_password
        tab = self._tablet(fn.attr)
        if tab is None or not fn.args:
            return _EMPTY
        plain = str(fn.args[0].value)
        scan = candidates if candidates is not None \
            else tab.src_uids(self.read_ts)
        keep = [u for u in scan.tolist()
                if any(verify_password(plain, str(p.value.value))
                       for p in tab.get_postings(u, self.read_ts))]
        return np.asarray(keep, dtype=np.uint64)

    def _eval_eq_tokens(self, tab: Optional[Tablet], vals: list[Val],
                        candidates, lang: str = "") -> np.ndarray:
        if tab is None:
            return _EMPTY
        with _span("eq", pred=tab.pred) as sp:
            return self._eval_eq_tokens_inner(tab, vals, candidates,
                                              lang, sp)

    def _eval_eq_tokens_inner(self, tab: Tablet, vals: list[Val],
                              candidates, lang: str = "",
                              sp: Optional[dict] = None) -> np.ndarray:
        out = _EMPTY
        # pick a non-lossy tokenizer if indexed (ref worker/task.go
        # pickTokenizer); else scan candidates' values
        spec = None
        for tname in tab.schema.tokenizers:
            s = get_tokenizer(tname)
            if not s.lossy:
                spec = s
                break
        if spec is None and tab.schema.indexed:
            spec = get_tokenizer(tab.schema.tokenizers[0])
        if spec is not None:
            # the query value must be analyzed the same way the indexed
            # values were: `eq(pred@de, ...)` uses the German analyzer;
            # `@.` (any language) probes every analyzer's buckets.
            # Token probes batch into ONE index probe + ONE k-way
            # union instead of per-token incremental union re-sorts

            def _analyze() -> tuple[list[bytes], list[Val]]:
                langs = _probe_langs(spec, lang)
                ntv: list[Val] = []
                toks_all: list[bytes] = []
                for v in vals:
                    v_toks = 0
                    for lg in langs:
                        try:
                            toks = tokens_for(v, spec, lg)
                        except (ValueError, TypeError):
                            continue
                        v_toks += len(toks)
                        toks_all.extend(token_bytes(spec.ident, t)
                                        for t in toks)
                    if not v_toks:
                        # a value no tokenizer emits tokens for (e.g.
                        # "") is absent from the index — PER VALUE,
                        # scan it below and union (ref
                        # TestQueryEmptyRoomsWithTermIndex; eq(room,
                        # ["", "green"]) must match both)
                        ntv.append(v)
                return toks_all, ntv

            if self.plan is not None:
                # token analysis is (schema, lang, literal)-derived —
                # exactly what a compiled plan binds once per
                # parameter vector (keyed by the VALUES: a shared
                # skeleton never serves another request's tokens)
                all_toks, no_tok_vals = self.plan.memo(
                    ("eqtok", tab.pred, lang, spec.ident,
                     tuple((v.tid, v.value) for v in vals)),
                    _analyze)
            else:
                all_toks, no_tok_vals = _analyze()
            dec = None
            # one clean posting, its length exact (None otherwise)
            est = self._posting_est(tab, all_toks[0]) \
                if len(all_toks) == 1 else None
            if all_toks and self._adaptive:
                # one token's posting length is known before the tier
                # is chosen, so the decision is made (and cached) for
                # THIS value's size: `eq(category, $c)` over values of
                # 1,700 and 103,000 rows is two decisions, not one
                # estimate that every other request violates
                from dgraph_tpu.query.planner import _bucket
                size = () if est is None else (_bucket(est["estRows"]),)
                tiers = self._index_tiers(tab)
                if est is not None:
                    # ... and that one posting is a slice of the CSR
                    # (or of the base index): there is no set
                    # operation whose blocks the packs could skip,
                    # only a decode of the whole list (0.7 ms for
                    # 21,000 uids against 1 us, and 5 ms where eight
                    # threads share the interpreter), so the packs are
                    # not a tier of this stage and the span's noisy
                    # wall time cannot drift a decision onto them
                    tiers = tuple(t for t in tiers if t != "compressed")
                dec = self._routed(
                    ("eq", tab.pred, len(all_toks)) + size,
                    lambda: self._tier_decision(
                        "eq", tab.pred,
                        est if est is not None
                        else self._token_est(tab, len(all_toks)),
                        tiers))
                if dec is not None and candidates is not None \
                        and not no_tok_vals \
                        and self.db.planner_impl.probe_or_scan(
                            "eq", dec.est_rows, len(candidates),
                            probe_tier=dec.tier) == "scan":
                    # index-probe vs candidate-scan pivot: the
                    # estimated token postings dwarf the candidate
                    # set, so verify the candidates' values directly
                    # (the exact filter semantics — the unindexed
                    # branch below — chosen on cost, not necessity)
                    if sp is not None:
                        sp["tier"] = "postings"
                        sp["n"] = int(len(candidates))
                    return self._eq_scan(tab, candidates, vals, lang)
            if all_toks:
                self._served_tier = None
                out = self._index_union(tab, all_toks,
                                        tier=dec.tier
                                        if dec is not None else None)
                self._record_outcome(dec, len(out))
                if sp is not None:
                    sp["tier"] = self._served_tier or "postings"
                    sp["n"] = int(len(out))
            if len(no_tok_vals) < len(vals):
                if spec.lossy or tab.schema.lang:
                    # @lang predicates share index buckets across
                    # language tags (the token carries no lang), so
                    # the index hit must be verified against the
                    # posting the query's lang selector actually
                    # addresses: eq(name, "") must not match a value
                    # that is empty only in @hi (ref query0_test.go
                    # TestQueryEmptyDefaultNames)
                    out = self._verify_eq(tab, out, vals, lang)
                if no_tok_vals:
                    scan = candidates if candidates is not None \
                        else tab.src_uids(self.read_ts)
                    extra = self._eq_scan(tab, scan, no_tok_vals, lang)
                    out = _union(out, extra)
                elif candidates is None and est is not None \
                        and not (spec.lossy or tab.schema.lang):
                    # `out` is one token's posting off a clean tablet
                    # at a read_ts its base serves: a later stage that
                    # is handed this very array may key what it keeps
                    # for it by where it came from (_posting_of)
                    self._clean_posting = (
                        out, (tab.pred, all_toks[0], tab.base_ts))
                return out if candidates is None \
                    else _intersect(candidates, out)
            # EVERY value was tokenless: plain scan below
        # unindexed: value scan over candidates (filter context) or all
        scan = candidates if candidates is not None \
            else tab.src_uids(self.read_ts)
        return self._eq_scan(tab, scan, vals, lang)

    def _eq_scan(self, tab, scan: np.ndarray, vals: list[Val],
                 lang: str = "") -> np.ndarray:
        """Equality scan over a sorted candidate vector: one vectorized
        column compare on clean tablets, per-uid postings otherwise."""
        got = self._eq_batch(tab, scan, vals, lang)
        if got is not None:
            return got
        return np.asarray(
            [u for u in scan.tolist()
             if self._value_matches_eq(tab, u, vals, lang)], np.uint64)

    def _eq_batch(self, tab, scan: np.ndarray, vals: list[Val],
                  lang: str = "") -> Optional[np.ndarray]:
        """Vectorized _value_matches_eq over the cached column view —
        the per-uid get_postings verify loop collapsed to one gather +
        one compare per query value. None keeps the exact path: dirty
        tablets, specific language tags (the untagged column can't
        answer them), datetime/geo columns, NUL-bearing payloads."""
        if lang not in ("", "."):
            return None
        colview = self._colview(tab)
        if colview is None:
            return None
        t = tab.schema.value_type
        if t == TypeID.DEFAULT:
            t = colview.tid if colview.tid != TypeID.DEFAULT \
                else TypeID.STRING
        if t not in (TypeID.STRING, TypeID.INT, TypeID.FLOAT,
                     TypeID.BOOL):
            return None
        if lang == ".":
            # '.' compares ANY posting: only string views track the
            # lang-tagged side (extra_*); a numeric tablet could carry
            # tagged postings the view never captured
            if t != TypeID.STRING or not colview.extra_ok:
                return None
        wants = []
        for v in vals:
            try:
                wants.append(convert(v, t).value)
            except ValueError:
                continue  # same skip as the per-posting loop
        pos, hit = _col_positions(colview.srcs, scan)
        sel = pos[hit]
        if t == TypeID.STRING:
            bc = colview.bytes_column()
            if bc is None:
                return None  # NUL-bearing payloads: exact path
            main_b, extra_b = bc
            col = main_b[sel]
            m = np.zeros(len(sel), bool)
            for w in wants:
                wb = str(w).encode("utf-8")
                if b"\x00" not in wb:  # a NUL-free column can't match
                    m |= col == wb
            parts = [scan[hit][m]]
            if lang == "." and len(colview.extra_srcs):
                em = np.isin(colview.extra_srcs, scan)
                ecol = extra_b[em]
                m2 = np.zeros(len(ecol), bool)
                for w in wants:
                    wb = str(w).encode("utf-8")
                    if b"\x00" not in wb:
                        m2 |= ecol == wb
                parts.append(np.unique(colview.extra_srcs[em][m2]))
            return setops.union_many(parts)
        col = colview.data[sel]
        m = np.zeros(len(sel), bool)
        for w in wants:
            try:
                m |= col == (int(w) if t == TypeID.BOOL else w)
            except (TypeError, OverflowError):
                continue
        return scan[hit][m]

    def _eval_eq_own_val(self, tab, fn: Function, candidates) -> np.ndarray:
        if tab is None:
            return _EMPTY
        vmap = {}
        for vc in fn.needs_var:
            vmap.update(self.value_vars.get(vc.name, {}))
        scan = candidates if candidates is not None \
            else _np_sorted(vmap.keys())
        keep = [u for u in scan.tolist()
                if u in vmap and self._value_matches_eq(tab, u, [vmap[u]])]
        return np.asarray(keep, dtype=np.uint64)

    def _verify_eq(self, tab, uids, vals, lang: str = "") -> np.ndarray:
        return self._eq_scan(tab, uids, vals, lang)

    def _value_matches_eq(self, tab: Tablet, uid: int,
                          vals: list[Val], lang: str = "") -> bool:
        for p in tab.get_postings(uid, self.read_ts):
            if not _lang_matches(p.lang, lang):
                continue
            for v in vals:
                try:
                    want = convert(v, self._cmp_type(tab, p))
                    have = convert(p.value, self._cmp_type(tab, p))
                except ValueError:
                    continue
                if have.value == want.value:
                    return True
        return False

    @staticmethod
    def _cmp_type(tab: Tablet, p) -> TypeID:
        t = tab.schema.value_type
        if t == TypeID.DEFAULT:
            t = p.value.tid if p.value.tid != TypeID.DEFAULT else TypeID.STRING
        return t

    def _eval_ineq(self, fn: Function, candidates) -> np.ndarray:
        with _span("ineq", fn=fn.name, pred=fn.attr) as sp:
            return self._eval_ineq_inner(fn, candidates, sp)

    def _ineq_est(self, tab, fname: str) -> dict:
        """EXPLAIN's range-fraction heuristic as the planner input
        (half the keys; a third for between), capped at keys + dirty
        slack."""
        st = self._tabstats(tab)
        if st is None:
            return {"estRows": -1, "estRowsMax": -1,
                    "basis": "unknown"}
        cap = st["nSrc"] + self._dirty_slack(tab)
        est = st["nSrc"] // (3 if fname == "between" else 2)
        return {"estRows": min(est, cap), "estRowsMax": cap,
                "basis": "stats", "source": "range-fraction heuristic"}

    def _eval_ineq_inner(self, fn: Function, candidates,
                         sp: Optional[dict] = None) -> np.ndarray:
        tab = self._tablet(fn.attr)
        ips = tab.schema if tab is not None \
            else self.db.schema.get(fn.attr)
        if candidates is None and ips is not None \
                and not fn.is_value_var \
                and ips.value_type != TypeID.BOOL \
                and not _has_sortable_index(ips):
            # schema-level check so declared-but-empty predicates
            # error like populated ones (ref worker/tokens.go
            # IsSortable requirement)
            raise GQLError(
                f"attribute {fn.attr!r} needs a sortable index "
                f"(exact/int/float/datetime) to serve {fn.name} "
                "at the query root")
        if tab is None:
            return _EMPTY
        tid = tab.schema.value_type
        if tid == TypeID.DEFAULT:
            tid = TypeID.STRING
        if fn.is_value_var:
            return self._eval_var_fn(fn, candidates)
        if tid == TypeID.BOOL:
            raise GQLError(
                f"attribute {fn.attr!r} is not sortable; only eq "
                "applies to bool values (ref TestBoolIndexgeRoot)")
        if fn.name != "between" and len(fn.args) > 1:
            # inequality against a value list is meaningless (ref
            # query1:TestMultipleGtError)
            raise GQLError(
                f"{fn.name}() expects a single value, "
                f"got {len(fn.args)}")
        def _bounds() -> tuple[int, int, bool, bool]:
            if fn.name == "between":
                return (sort_key(convert(
                            Val(TypeID.DEFAULT, fn.args[0].value), tid)),
                        sort_key(convert(
                            Val(TypeID.DEFAULT, fn.args[1].value), tid)),
                        False, False)
            bound = sort_key(
                convert(Val(TypeID.DEFAULT, fn.args[0].value), tid))
            b_lo, b_hi = -(1 << 63), (1 << 63) - 1
            b_lo_open = b_hi_open = False
            if fn.name == "le":
                b_hi = bound
            elif fn.name == "lt":
                b_hi, b_hi_open = bound, True
            elif fn.name == "ge":
                b_lo = bound
            else:
                b_lo, b_lo_open = bound, True
            return b_lo, b_hi, b_lo_open, b_hi_open

        try:
            if self.plan is not None:
                # bound parsing (datetime/float literal -> int64 sort
                # key) is (literal, type)-pure: bind once per params
                lo, hi, lo_open, hi_open = self.plan.memo(
                    ("ineq", fn.name, fn.attr, int(tid),
                     tuple(a.value for a in fn.args)),
                    _bounds)
            else:
                lo, hi, lo_open, hi_open = _bounds()
        except ValueError as e:
            raise GQLError(f"bad {fn.name} argument for {fn.attr}: {e}")
        # strings compare beyond the 8-byte key prefix: exact host compare
        if tid in (TypeID.STRING, TypeID.DEFAULT):
            return self._ineq_scan_strings(tab, fn, candidates)
        # tier choice: device range kernel / cached sort-key arrays /
        # exact per-uid walk. The planner decides from estimated rows
        # x observed cost; device_min_edges <= 1 (the force override)
        # and the static mode keep the measured-RTT gate.
        dec = tier = None
        if self._adaptive and self.db.device_min_edges > 1:
            def _build_ineq():
                avail = ["postings"]
                if self._columnar_on() \
                        and hasattr(tab, "sort_key_arrays"):
                    avail.append("columnar")
                if self.db.prefer_device \
                        and self.db.device_is_accelerator():
                    avail.append("device")
                return self._tier_decision(
                    "ineq", fn.attr, self._ineq_est(tab, fn.name),
                    tuple(avail))
            dec = self._routed(("ineq", fn.attr, fn.name), _build_ineq)
            tier = dec.tier if dec is not None else None
        if (tier == "device") if dec is not None else (
                self.db.prefer_device and self._device_worth(
                    len(getattr(tab, "values", ()))
                    * self._HOST_PER_RANGE_VAL,
                    device_ratio=self._DEVICE_RATIO_RANGE)):
            dev = self._device_range(tab, lo, hi, lo_open, hi_open)
            if dev is not None:
                self._record_outcome(dec, len(dev))
                if sp is not None:
                    sp["tier"] = "device"
                    sp["n"] = int(len(dev))
                return dev if candidates is None \
                    else _intersect(candidates, dev)
        if tier == "postings" \
                or not hasattr(tab, "sort_key_arrays") \
                or self.read_ts < tab.base_ts \
                or not self._columnar_on():
            served = "postings"
            pairs = self._sortkeys_for(tab)
            uids = np.fromiter(pairs.keys(), np.uint64, len(pairs))
            keys = np.fromiter(pairs.values(), np.int64, len(pairs))
            order = np.argsort(uids, kind="stable")
            uids, keys = uids[order], keys[order]
        elif tab.dirty():
            served = "columnar"
            uids, keys = self._sortkeys_dirty(tab)
        else:
            served = "columnar"
            uids, keys = tab.sort_key_arrays()
        if not len(uids):
            self._record_outcome(dec, 0)
            return _EMPTY

        def in_range(kk):
            return (kk > lo if lo_open else kk >= lo) & \
                (kk < hi if hi_open else kk <= hi)

        if candidates is not None \
                and len(uids) >= 2 * len(candidates):
            # filter context with a narrower candidate set: gather the
            # candidates' keys instead of masking the whole tablet
            # column and re-intersecting (the q003-at-21M shape)
            pos, hit = _col_positions(uids, candidates)
            kk = keys[pos[hit]]
            out = candidates[hit][in_range(kk)]
            self._record_outcome(dec, len(out))
            if sp is not None:
                sp["tier"] = served
                sp["n"] = int(len(out))
            return out
        out = np.sort(uids[in_range(keys)])
        self._record_outcome(dec, len(out))
        if sp is not None:
            sp["tier"] = served
            sp["n"] = int(len(out))
        return out if candidates is None else _intersect(candidates, out)

    def _sortkeys_dirty(self, tab) -> tuple[np.ndarray, np.ndarray]:
        """(uids, int64 sort keys) of a DIRTY tablet at read_ts: the
        cached base arrays answer every overlay-untouched row; touched
        rows re-read through the exact MVCC posting path and merge —
        the same immutable/mutable split the device tiles use (ref
        posting/mvcc.go). Replaces a full per-uid dict rebuild per
        query on bulk-mutated stores."""
        buids, bkeys = tab.sort_key_arrays()
        touched = tab.overlay_srcs(self.read_ts)
        if touched:
            tarr = np.fromiter(touched, np.uint64, len(touched))
            keep = ~np.isin(buids, tarr)
            buids, bkeys = buids[keep], bkeys[keep]
            ou: list[int] = []
            ok: list[int] = []
            for u in sorted(touched):
                for p in tab.get_postings(int(u), self.read_ts):
                    if p.lang:
                        continue
                    try:
                        ok.append(sort_key(convert(
                            p.value, tab.schema.value_type
                            if tab.schema.value_type != TypeID.DEFAULT
                            else p.value.tid)))
                        ou.append(int(u))
                    except ValueError:
                        pass
                    break
            if ou:
                buids = np.concatenate(
                    [buids, np.asarray(ou, np.uint64)])
                bkeys = np.concatenate(
                    [bkeys, np.asarray(ok, np.int64)])
                order = np.argsort(buids, kind="stable")
                buids, bkeys = buids[order], bkeys[order]
        return buids, bkeys

    def _device_range(self, tab, lo, hi, lo_open, hi_open
                      ) -> Optional[np.ndarray]:
        """le/lt/ge/gt/between root scan as one device mask + compact
        (ops/graph.range_select; ref worker/tokens.go:113)."""
        from dgraph_tpu.engine.device_cache import device_values
        from dgraph_tpu.ops.graph import range_select
        from dgraph_tpu.ops.uidvec import to_numpy

        dv = device_values(self.db, tab, self.read_ts)
        if dv is None:
            return None
        with device_call("query_device_range_total", sink=self.lat,
                         program="range_select") as dc:
            out = dc.wait(range_select(dv, lo, hi, lo_open, hi_open))
            return to_numpy(out).astype(np.uint64)

    def _ineq_scan_strings(self, tab, fn, candidates) -> np.ndarray:
        want = str(fn.args[0].value)
        hi2 = str(fn.args[1].value) if fn.name == "between" else None
        op = fn.name
        keep = []
        scan = candidates if candidates is not None \
            else tab.src_uids(self.read_ts)
        batched = self._ineq_strings_batch(tab, scan, fn, want, hi2)
        if batched is not None:
            return batched
        for u in scan.tolist():
            for p in tab.get_postings(u, self.read_ts):
                if not _lang_matches(p.lang, fn.lang or ""):
                    # lt(name, v) compares the UNTAGGED value only;
                    # lt(name@de, v) the @de one (ref query0_test.go
                    # TestQueryNamesBeforeA: a value empty only in
                    # @hi must not satisfy lt(name, "A"))
                    continue
                s = str(p.value.value)
                ok = ((op == "le" and s <= want) or (op == "lt" and s < want)
                      or (op == "ge" and s >= want) or (op == "gt" and s > want)
                      or (op == "between" and want <= s <= hi2))
                if ok:
                    keep.append(u)
                    break
        return np.asarray(keep, dtype=np.uint64)

    _INEQ_VEC = {
        "le": lambda col, lo, hi: col <= lo,
        "lt": lambda col, lo, hi: col < lo,
        "ge": lambda col, lo, hi: col >= lo,
        "gt": lambda col, lo, hi: col > lo,
        "between": lambda col, lo, hi: (col >= lo) & (col <= hi),
    }

    def _ineq_strings_batch(self, tab, scan, fn, want: str,
                            hi2) -> Optional[np.ndarray]:
        """String inequality over the cached byte columns: UTF-8 byte
        order IS codepoint order, so fixed-width byte compares equal
        the host loop's str compares. Exact path stays for dirty
        tablets, specific language tags and NUL-bearing payloads."""
        lang = fn.lang or ""
        if lang not in ("", "."):
            return None
        colview = self._colview(tab)
        if colview is None \
                or colview.tid not in (TypeID.STRING, TypeID.DEFAULT):
            return None
        if lang == "." and not colview.extra_ok:
            return None
        bc = colview.bytes_column()
        if bc is None:
            return None
        wb = want.encode("utf-8")
        hb = hi2.encode("utf-8") if hi2 is not None else None
        cmp = self._INEQ_VEC[fn.name]
        main_b, extra_b = bc
        pos, hit = _col_positions(colview.srcs, scan)
        parts = [scan[hit][cmp(main_b[pos[hit]], wb, hb)]]
        if lang == "." and len(colview.extra_srcs):
            em = np.isin(colview.extra_srcs, scan)
            m2 = cmp(extra_b[em], wb, hb)
            parts.append(np.unique(colview.extra_srcs[em][m2]))
        return setops.union_many(parts)

    def _sortkeys_for(self, tab: Tablet) -> dict[int, int]:
        out = {}
        if tab.dirty():
            for u in tab.src_uids(self.read_ts).tolist():
                for p in tab.get_postings(u, self.read_ts):
                    if p.lang:
                        continue
                    try:
                        out[u] = sort_key(convert(
                            p.value, tab.schema.value_type
                            if tab.schema.value_type != TypeID.DEFAULT
                            else p.value.tid))
                    except ValueError:
                        pass
                    break
            return out
        return tab.sort_key_pairs()

    def _eval_terms(self, fn: Function, candidates) -> np.ndarray:
        with _span("setops", fn=fn.name, pred=fn.attr) as sp:
            return self._eval_terms_inner(fn, candidates, sp)

    def _eval_terms_inner(self, fn: Function, candidates,
                          sp: Optional[dict] = None) -> np.ndarray:
        tab = self._tablet(fn.attr)
        toker = "fulltext" if fn.name in ("anyoftext", "alloftext") else "term"
        ps = tab.schema if tab is not None \
            else self.db.schema.get(fn.attr)
        if ps is not None and toker not in ps.tokenizers:
            # the functions read the index buckets; without the
            # matching tokenizer there is nothing to read — a SCHEMA
            # property, checked whether or not data exists yet (ref
            # query4:TestDeleteAndReaddIndex "Attribute ... is not
            # indexed with type fulltext")
            raise GQLError(
                f"attribute {fn.attr!r} is not indexed with type "
                f"{toker} (required by {fn.name})")
        if tab is None:
            return _EMPTY
        spec = get_tokenizer(toker)
        text = " ".join(a.value for a in fn.args)
        # `pred@.` (any language): a value matches if it satisfies the
        # all/any condition under at least one language's analyzer —
        # per-analyzer evaluation, then union. Each analyzer's token
        # probe is one batched CSR slice + one k-way set op
        # (ops/setops) instead of a pairwise union/intersect fold
        dec = None
        if self._adaptive:
            n_terms = len(text.split()) or 1
            dec = self._routed(
                ("setops", fn.attr, fn.name, n_terms),
                lambda: self._tier_decision(
                    "setops", fn.attr,
                    self._token_est(tab, 1 if fn.name.startswith("all")
                                    else n_terms),
                    self._index_tiers(tab)))
        tier = dec.tier if dec is not None else None
        self._served_tier = None
        parts: list[np.ndarray] = []
        for lg in _probe_langs(spec, fn.lang or ""):
            if self.plan is not None:
                # term analysis is (analyzer, literal)-pure — a warm
                # plan binds the token batch once per parameter vector
                toks = self.plan.memo(
                    ("terms", toker, lg, text),
                    lambda: tokens_for(Val(TypeID.STRING, text),
                                       spec, lg))
            else:
                toks = tokens_for(Val(TypeID.STRING, text), spec, lg)
            if not toks:
                continue
            tbs = [token_bytes(spec.ident, t) for t in toks]
            if fn.name.startswith("all"):
                parts.append(self._index_intersect(tab, tbs, tier))
            else:
                parts.append(self._index_union(tab, tbs, tier))
        out = self._union_many(parts)
        self._record_outcome(dec, len(out))
        if sp is not None:
            sp["tier"] = self._served_tier or "postings"
            sp["n"] = int(len(out))
        return out if candidates is None else _intersect(candidates, out)

    def _eval_anyof(self, fn: Function, candidates) -> np.ndarray:
        with _span("setops", fn=fn.name, pred=fn.attr) as sp:
            return self._eval_anyof_inner(fn, candidates, sp)

    def _eval_anyof_inner(self, fn: Function, candidates,
                          sp: Optional[dict] = None) -> np.ndarray:
        """anyof/allof(pred, tokenizer, v...): generic token match with
        an explicitly named (usually custom plugin) tokenizer — the
        custom-tokenizer query surface (ref worker/task.go:260 anyof/
        allof cases; systest/plugin_test.go usage)."""
        tab = self._tablet(fn.attr)
        if tab is None:
            return _EMPTY
        if len(fn.args) < 2:
            raise GQLError(
                f"{fn.name} requires a tokenizer name and a value")
        tokname = str(fn.args[0].value)
        spec = get_tokenizer(tokname)
        if tokname not in (tab.schema.tokenizers or []):
            raise GQLError(
                f"attribute {fn.attr!r} is not indexed with "
                f"tokenizer {tokname!r}")
        toks: list = []
        for a in fn.args[1:]:
            toks.extend(tokens_for(
                Val(TypeID.STRING, str(a.value)), spec))
        if not toks:
            return _EMPTY
        tbs = [token_bytes(spec.ident, t) for t in toks]
        dec = None
        if self._adaptive:
            dec = self._routed(
                ("setops", fn.attr, fn.name, len(tbs)),
                lambda: self._tier_decision(
                    "setops", fn.attr,
                    self._token_est(tab, 1 if fn.name == "allof"
                                    else len(tbs)),
                    self._index_tiers(tab)))
        tier = dec.tier if dec is not None else None
        self._served_tier = None
        if fn.name == "allof":
            got = self._index_intersect(tab, tbs, tier)
        else:
            got = self._index_union(tab, tbs, tier)
        self._record_outcome(dec, len(got))
        if sp is not None:
            sp["tier"] = self._served_tier or "postings"
            sp["n"] = int(len(got))
        return got if candidates is None else _intersect(candidates, got)

    def _eval_regexp(self, fn: Function, candidates) -> np.ndarray:
        """Trigram-index prefilter + host regex verify
        (ref worker/trigram.go:35 + task.go:1001)."""
        tab = self._tablet(fn.attr)
        if tab is None:
            return _EMPTY
        pattern = fn.args[0].value
        flags = _re.IGNORECASE if (len(fn.args) > 1
                                   and "i" in fn.args[1].value) else 0
        if self.plan is not None:
            # regex + trigram-query compilation is pure in (pattern,
            # flags): a compiled plan binds it once per literal
            rx, triq = self.plan.memo(
                ("regexp", pattern, flags),
                lambda: (_re.compile(pattern, flags),
                         compile_trigram_query(pattern, flags)))
        else:
            rx = _re.compile(pattern, flags)
            triq = None
        indexed = tab.schema.indexed and "trigram" in tab.schema.tokenizers
        if indexed and candidates is None:
            # Compile the regex AST into an AND/OR trigram query — a
            # necessary condition per alternation branch — and walk the
            # index with it (ref worker/trigram.go:35 uidsForRegex via
            # cindex.RegexpQuery).  ALL ⇒ no index help ⇒ full scan.
            q = triq if triq is not None \
                else compile_trigram_query(pattern, flags)
            dec = self._trigram_tier(tab, "regexp", 3)
            # the trigram walk opens a setops span so every tier's
            # cost lands in the coststore — without cells the
            # planner's rival check has no evidence to correct a
            # cold-prior pick with
            with _span("setops", fn="regexp", pred=tab.pred) as tsp:
                self._served_tier = None
                cand = self._trigram_query_uids(
                    tab, q, dec.tier if dec is not None else None)
                if cand is not None:
                    self._record_outcome(dec, len(cand))
                    tsp["n"] = int(len(cand))
                tsp["tier"] = self._served_tier or "postings"
            scan = cand if cand is not None else tab.src_uids(self.read_ts)
        else:
            scan = candidates if candidates is not None \
                else tab.src_uids(self.read_ts)
        batched = self._regexp_batch(tab, scan, pattern, flags)
        if batched is not None:
            return batched
        keep = []
        for u in scan.tolist():
            for p in tab.get_postings(u, self.read_ts):
                if rx.search(str(p.value.value)):
                    keep.append(u)
                    break
        return np.asarray(keep, dtype=np.uint64)

    def _trigram_query_uids(self, tab, q,
                            tier: Optional[str] = None
                            ) -> Optional[np.ndarray]:
        """Evaluate a compiled TriQuery against `tab`'s trigram index.
        Returns None for an unconstrained (ALL) query — caller scans —
        so an ALL branch inside an OR correctly un-constrains the whole
        OR, as in the reference's trigram query algebra. `tier` (the
        planner's pick) routes every probe batch."""
        spec = get_tokenizer("trigram")

        def ev(node) -> Optional[np.ndarray]:
            if node.op == "all":
                return None
            if node.op == "none":
                return _EMPTY
            if node.op == "and":
                parts = []
                if node.trigrams:
                    # one compressed/batched k-token AND: block-
                    # descriptor skipping prunes non-overlapping
                    # posting blocks before any decode
                    first = self._index_intersect(
                        tab, [token_bytes(spec.ident, t)
                              for t in node.trigrams], tier)
                    if first.size == 0:
                        return first  # dead branch: skip the subs
                    parts = [first]
                for s in node.subs:
                    got = ev(s)
                    if got is not None:
                        parts.append(got)
                if not parts:
                    return None  # every child unconstrained
                return self._intersect_many(parts)
            # OR
            parts = [self._index_union(
                tab, [token_bytes(spec.ident, t)
                      for t in node.trigrams], tier)] \
                if node.trigrams else []
            for s in node.subs:
                got = ev(s)
                if got is None:
                    return None
                parts.append(got)
            return self._union_many(parts)

        return ev(q)

    def _regexp_batch(self, tab, scan, pattern: str,
                      flags) -> Optional[np.ndarray]:
        """Regex verify over the clean tablet's pre-encoded column
        payloads (bytes-level re for ASCII patterns — identical
        semantics, no get_postings walk per uid). Lang-tagged extras
        verify in the same pass, so mixed uids match like the host
        loop."""
        colview = self._colview(tab)
        if colview is None or colview.enc is None \
                or colview.tid not in (TypeID.STRING, TypeID.DEFAULT) \
                or not colview.extra_ok or not colview.ascii_only \
                or any(ord(c) > 127 for c in pattern):
            return None
        try:
            rxb = _re.compile(pattern.encode("ascii"), flags)
        except _re.error:
            return None
        srcs, _tid, _data, enc = colview
        pos, hit = _col_positions(srcs, scan)
        search = rxb.search
        keep = [np.asarray(
            [u for u, j in zip(scan[hit].tolist(), pos[hit].tolist())
             if search(enc[j])], np.uint64)]
        if len(colview.extra_srcs):
            em = np.isin(colview.extra_srcs, scan)
            keep.append(np.asarray(
                [u for u, j in zip(colview.extra_srcs[em].tolist(),
                                   np.nonzero(em)[0].tolist())
                 if search(colview.extra_enc[j])], np.uint64))
        inc_counter("query_regexp_batch_total")
        return np.unique(np.concatenate(keep))

    def _eval_match(self, fn: Function, candidates) -> np.ndarray:
        """Fuzzy match: trigram-index candidate narrowing + Levenshtein
        verify (ref worker/match.go uidsForMatch — the index UNION of
        the term's trigrams — then matchFuzzy; default max distance 8).
        Unindexed predicates fall back to a full scan, a superset of
        the reference (which rejects match() without @index(trigram))."""
        tab = self._tablet(fn.attr)
        if tab is None:
            return _EMPTY
        want = fn.args[0].value
        maxd = int(fn.args[1].value) if len(fn.args) > 1 else 8
        scan = candidates
        if scan is None:
            spec = get_tokenizer("trigram")
            if tab.schema.indexed and \
                    "trigram" in tab.schema.tokenizers:
                # candidates = UNION of the term's trigram buckets —
                # the reference's own candidate set (worker/match.go
                # uidsForMatch): values sharing no trigram with the
                # term are out, exactly like the reference. Terms too
                # short to produce a trigram keep the full scan.
                toks = tokens_for(Val(TypeID.STRING, want), spec)
                if toks:
                    # q-gram COUNT filter: a value within edit
                    # distance d of the term must share at least
                    # T - 3d of its T distinct trigrams (each edit
                    # destroys <= 3 windows) — at 21M this prunes the
                    # "shares any trigram" union from ~2M candidates
                    # to thousands. Compressed tier: posting blocks
                    # held by < need trigrams skip without decode.
                    need = max(1, len(toks) - 3 * maxd)
                    dec = self._trigram_tier(tab, "match", len(toks))
                    with _span("setops", fn="match",
                               pred=tab.pred) as tsp:
                        self._served_tier = None
                        scan = self._index_count_filter(
                            tab, [token_bytes(spec.ident, t)
                                  for t in toks], need,
                            dec.tier if dec is not None else None)
                        tsp["tier"] = self._served_tier or "postings"
                        tsp["n"] = int(len(scan))
                    self._record_outcome(dec, len(scan))
        if scan is None:
            scan = tab.src_uids(self.read_ts)
        batched = self._match_batch(tab, scan, want, maxd)
        if batched is not None:
            return batched
        return self._match_scan(tab, scan, want, maxd)

    def _match_scan(self, tab, scan, want: str, maxd: int) -> np.ndarray:
        # case-sensitive over code points, like the reference's
        # levenshteinDistance (worker/match.go:35 — no lowering)
        keep = []
        for u in scan.tolist():
            for p in tab.get_postings(u, self.read_ts):
                if _levenshtein(str(p.value.value), want,
                                maxd) <= maxd:
                    keep.append(u)
                    break
        return np.asarray(keep, dtype=np.uint64)

    def _match_batch(self, tab, scan, want: str,
                     maxd: int) -> Optional[np.ndarray]:
        with _span("match", pred=tab.pred, n=len(scan)):
            return self._match_batch_inner(tab, scan, want, maxd)

    def _match_batch_inner(self, tab, scan, want: str,
                           maxd: int) -> Optional[np.ndarray]:
        """Verify all candidates in ONE native call over the columnar
        string view (C loop + banded Levenshtein) instead of a per-uid
        get_postings round — 21M-regime q015 spends ~45s in the Python
        loop otherwise. Lang-tagged postings (absent from the untagged
        column) re-verify on the exact host path, so tagged-only and
        mixed uids match identically to _match_scan."""
        from dgraph_tpu import native as _native

        colview = self._colview(tab)
        if colview is None or colview.enc is None \
                or colview.tid not in (TypeID.STRING, TypeID.DEFAULT) \
                or not colview.extra_ok:
            return None
        if not _native.available():
            return self._match_batch_np(colview, scan, want, maxd)
        srcs, _tid, _data, enc = colview

        def masked(cand_srcs, payloads):
            offs = np.zeros(len(payloads) + 1, np.int64)
            np.cumsum([len(e) for e in payloads], out=offs[1:])
            blob = np.frombuffer(b"".join(payloads), np.uint8) \
                if payloads else np.zeros(1, np.uint8)
            m = _native.match_mask(want.encode("utf-8"), maxd, blob,
                                   offs)
            return None if m is None else cand_srcs[m == 1]

        pos, hit = _col_positions(srcs, scan)
        sel = pos[hit]
        blob, boffs = colview.payload_blob()
        m = _native.match_mask_idx(want.encode("utf-8"), maxd,
                                   blob, boffs, sel)
        if m is None:
            return None
        got = scan[hit][m == 1]
        keep = [got]
        if len(colview.extra_srcs):
            # lang-tagged payloads of candidate uids, same batch call
            em = np.isin(colview.extra_srcs, scan)
            egot = masked(colview.extra_srcs[em],
                          [colview.extra_enc[j]
                           for j in np.nonzero(em)[0].tolist()])
            if egot is None:
                return None
            keep.append(egot)
        inc_counter("query_match_batch_total")
        out = np.unique(np.concatenate(keep))
        return out

    def _match_batch_np(self, colview, scan, want: str,
                        maxd: int) -> Optional[np.ndarray]:
        """match() verify without the native extension: Myers
        bit-parallel edit distance (ops/editdist) over the cached byte
        matrix — every candidate in ~15 numpy ops per payload column
        instead of a per-uid python DP (the whole q015 budget when the
        C++ kernel isn't built). Byte scores equal codepoint distances
        only for ASCII rows; the kernel flags the rest (-1) and they
        re-verify on the exact path."""
        from dgraph_tpu.ops.editdist import levenshtein_scores

        if not want or not want.isascii() or len(want) > 63:
            return None  # outside the bit-parallel kernel's domain
        bc = colview.bytes_column()
        if bc is None:
            return None
        main_b, extra_b = bc

        m = len(want)

        def verify(cand_uids, barr, enc_list, idx):
            if not len(cand_uids):
                return cand_uids
            sub = np.ascontiguousarray(barr)
            mat = sub.view(np.uint8).reshape(
                len(sub), sub.dtype.itemsize)
            lens = np.char.str_len(sub)
            # length band: |len(b) - len(a)| > maxd means distance >
            # maxd. Byte length >= codepoint count, so the LOW side is
            # exact for every row; the high side is exact only for
            # ASCII rows — longer non-ASCII rows re-verify exactly
            low = lens < m - maxd
            up = lens > m + maxd
            run = ~(low | up)
            keep = np.zeros(len(cand_uids), bool)
            if run.any():
                ridx = np.nonzero(run)[0]
                scores = levenshtein_scores(want, mat[ridx],
                                            lens[ridx])
                if scores is None:
                    return None
                keep[ridx[(scores >= 0) & (scores <= maxd)]] = True
                for i in ridx[scores == -1].tolist():
                    s = enc_list[int(idx[i])].decode("utf-8")
                    if _levenshtein(s, want, maxd) <= maxd:
                        keep[i] = True
            if up.any():
                uidx = np.nonzero(up)[0]
                for i in uidx[(mat[uidx] >= 0x80).any(axis=1)].tolist():
                    s = enc_list[int(idx[i])].decode("utf-8")
                    if _levenshtein(s, want, maxd) <= maxd:
                        keep[i] = True
            return cand_uids[keep]

        pos, hit = _col_positions(colview.srcs, scan)
        sel = pos[hit]
        got = verify(scan[hit], main_b[sel], colview.enc, sel)
        if got is None:
            return None
        parts = [got]
        if len(colview.extra_srcs):
            em = np.isin(colview.extra_srcs, scan)
            eidx = np.nonzero(em)[0]
            egot = verify(colview.extra_srcs[em], extra_b[em],
                          colview.extra_enc, eidx)
            if egot is None:
                return None
            parts.append(np.unique(egot))
        inc_counter("query_match_batch_total")
        return setops.union_many(parts)

    def _eval_uid_in(self, fn: Function, candidates) -> np.ndarray:
        """uid_in(pred, uids) — also over reverse edges: uid_in(~pred, X)
        keeps uids that X points at via pred (ref worker/task.go
        handleUidPostings UidInFn; reverse attrs resolve like any
        predicate)."""
        if candidates is None:
            # filter-only, like the reference (query1:
            # TestUidInFunctionAtRoot rejects it at the root)
            raise GQLError(
                "the uid_in function is only valid in @filter")
        rev = fn.attr.startswith("~")
        tab = self._tablet(fn.attr[1:] if rev else fn.attr)
        if tab is None:
            return _EMPTY
        if rev and not tab.schema.reverse:
            raise GQLError(
                f"uid_in: no reverse index on {fn.attr[1:]!r} "
                f"(add @reverse to the schema)")
        targets = set(fn.uids)
        for vc in fn.needs_var:
            targets.update(self.uid_vars.get(vc.name, _EMPTY).tolist())
        # Flip the iteration: expand from the (few) TARGETS and
        # intersect with the candidate set instead of walking every
        # candidate's edge list — uid_in over 960k candidates at 21M
        # was ~0.8s of per-uid python. uid_in(~p, X) keeps uids X
        # points at via p (= dst(X)); uid_in(p, X) keeps uids pointing
        # AT some X (= reverse(X), when @reverse exists).
        flip = rev or tab.schema.reverse
        if flip and candidates is not None \
                and len(targets) > len(candidates):
            flip = False  # per-candidate walk is the cheaper direction
        if flip:
            expand = tab.get_dst_uids if rev else tab.get_reverse_uids
            parts = [expand(int(t), self.read_ts) for t in targets]
            parts = [p for p in parts if len(p)]
            if not parts:
                return _EMPTY
            valid = np.unique(np.concatenate(parts))
            # valid uids have a live edge by construction, so with no
            # candidate set they ARE the answer — don't materialize
            # the whole src/dst table just to intersect with a subset
            return valid if candidates is None \
                else _intersect(candidates, valid)
        scan = candidates if candidates is not None else (
            tab.dst_uids(self.read_ts) if rev
            else tab.src_uids(self.read_ts))
        getter = tab.get_reverse_uids if rev else tab.get_dst_uids
        keep = [u for u in scan.tolist()
                if targets & set(getter(u, self.read_ts).tolist())]
        return np.asarray(keep, dtype=np.uint64)

    def _eval_count_fn(self, fn: Function, candidates) -> np.ndarray:
        """gt(count(friend), 2) etc (ref task.go:1111 handleCompare +
        count index). Vectorized over the base count table; only
        overlay-touched uids fall back to per-uid MVCC counting.
        count(~pred) counts incoming edges (ref query2_test.go
        TestCountReverseFunc; needs @reverse)."""
        if fn.attr.startswith("~"):
            tab = self._tablet(fn.attr[1:])
            rps = tab.schema if tab is not None \
                else self.db.schema.get(fn.attr[1:])
            if candidates is None and rps is not None \
                    and not rps.count:
                raise GQLError(
                    f"need @count directive in schema for attribute "
                    f"{fn.attr[1:]!r} to serve count comparisons at "
                    "the root")
            if tab is None:
                return self._count_zero_case(fn, candidates)
            if not tab.schema.reverse:
                raise GQLError(
                    f"count(~{fn.attr[1:]}) needs @reverse on "
                    f"{fn.attr[1:]!r}")
            scan = candidates if candidates is not None else \
                tab.dst_uids(self.read_ts)

            def ok(n: int) -> bool:
                if fn.name == "between":
                    return int(fn.args[0].value) <= n <= \
                        int(fn.args[1].value)
                return _cmp(fn.name, n, int(fn.args[0].value))

            keep = np.asarray(
                [u for u in scan.tolist()
                 if ok(len(tab.get_reverse_uids(int(u),
                                                self.read_ts)))],
                dtype=np.uint64)
            keep.sort()
            return keep
        tab = self._tablet(fn.attr)
        ps = tab.schema if tab is not None \
            else self.db.schema.get(fn.attr)
        if candidates is None and ps is not None and not ps.count:
            # a root count comparison walks the count index: every
            # predicate — uid ones included — needs @count, and the
            # requirement is a SCHEMA property independent of whether
            # data exists yet (ref query4:TestDeleteAndReaddCount
            # "Need @count directive in schema for attr")
            raise GQLError(
                f"need @count directive in schema for attribute "
                f"{fn.attr!r} to serve count comparisons at the root")
        if tab is None:
            # every candidate has count 0: let the zero-case decide
            # whether 0 satisfies the comparison (ge(count(x), 0) does)
            return self._count_zero_case(fn, candidates)
        want = int(fn.args[0].value)
        cmp_name = fn.name
        if fn.name == "between":
            # between(count(p), lo, hi): vector range mask; the scalar
            # fallback closes over the same bounds
            lo, hi = want, int(fn.args[1].value)
            vec = lambda a, b: (a >= lo) & (a <= hi)  # noqa: E731
        elif fn.name in _CMP_VEC:
            vec = _CMP_VEC[fn.name]
        else:
            raise GQLError(f"bad count comparison {fn.name}")
        scan = candidates if candidates is not None else \
            tab.src_uids(self.read_ts)
        if not len(scan):
            return _EMPTY
        touched = tab.overlay_srcs(self.read_ts) if tab.dirty() \
            else set()
        srcs, counts = tab.count_table()
        if touched:
            tarr = np.fromiter(touched, np.uint64, len(touched))
            dirty_mask = np.isin(scan, tarr)
            clean = scan[~dirty_mask]
            dirty = scan[dirty_mask]
        else:
            clean, dirty = scan, scan[:0]
        # clean uids: one searchsorted lookup + one vector compare
        if len(srcs):
            idx = np.clip(np.searchsorted(srcs, clean), 0, len(srcs) - 1)
            hit = srcs[idx] == clean
            cnts = np.where(hit, counts[idx], 0)
        else:
            cnts = np.zeros(len(clean), np.int64)
        ok = vec(cnts, want)
        keep = [clean[ok]]
        # overlay-touched uids: exact per-uid MVCC count
        keep.append(np.asarray(
            [u for u in dirty.tolist()
             if vec(tab.count_of(u, self.read_ts), want)],
            dtype=np.uint64))
        out = np.concatenate(keep)
        out.sort()
        return out

    def _count_zero_case(self, fn, candidates):
        if candidates is None:
            return _EMPTY
        if fn.name == "between":
            lo, hi = int(fn.args[0].value), int(fn.args[1].value)
            return candidates if lo <= 0 <= hi else _EMPTY
        if _cmp(fn.name, 0, int(fn.args[0].value)):
            return candidates
        return _EMPTY

    def _eval_var_fn(self, fn: Function, candidates) -> np.ndarray:
        """eq/ineq over val(v) or len(v) (ref query.go shortest var
        filtering + parser IsValueVar)."""
        if fn.is_len_var:
            vc = fn.needs_var[0]
            n = len(self.uid_vars.get(vc.name, _EMPTY))
            if vc.name in self.value_vars:
                n = len(self.value_vars[vc.name])
            ok = _cmp(fn.name, n, int(fn.args[0].value))
            if candidates is None:
                return _EMPTY
            return candidates if ok else _EMPTY
        vc = fn.needs_var[0]
        vmap = self.value_vars.get(vc.name, {})
        want_raw = fn.args[0].value if fn.args else None
        scan = candidates if candidates is not None else _var_domain(vmap)
        if isinstance(vmap, ColVar) and not vmap.frac \
                and vmap.tid != TypeID.DATETIME \
                and fn.name in _CMP_VEC:
            # columnar filter: one gather + one vector compare (ref
            # query.go val-var filters; the dict walk remains only for
            # mixed-typed math results where per-uid tids differ)
            vtid = TypeID.BOOL if vmap.isbool else vmap.tid
            try:
                want = convert(Val(TypeID.DEFAULT, want_raw), vtid).value
            except ValueError:
                return _EMPTY
            uids, vals = vmap.gather(scan)
            if vtid == TypeID.BOOL:
                vals, want = vals.astype(bool), bool(want)
            ok = _CMP_VEC[fn.name](vals, want)
            return uids[ok]
        keep = []
        for u in scan.tolist():
            v = vmap.get(u)
            if v is None:
                continue
            try:
                want = convert(Val(TypeID.DEFAULT, want_raw), v.tid).value
            except ValueError:
                continue
            if _cmp(fn.name, v.value, want):
                keep.append(u)
        return np.asarray(keep, dtype=np.uint64)

    # ------------------------------------------------------------------
    # filters (ref query.go:2078)
    # ------------------------------------------------------------------

    def _eval_filter(self, ft: FilterTree, candidates: np.ndarray
                     ) -> np.ndarray:
        if ft.func is not None:
            return self._eval_func(ft.func, candidates)
        if ft.op == "and":
            out = candidates
            for c in ft.children:
                out = self._eval_filter(c, out)
            return out
        if ft.op == "or":
            # k-way: one merge over every branch instead of a pairwise
            # accumulator re-sort per child (ref algo.MergeSorted)
            return self._union_many(
                [self._eval_filter(c, candidates)
                 for c in ft.children])
        if ft.op == "not":
            sub = self._eval_filter(ft.children[0], candidates)
            return _difference(candidates, sub)
        raise GQLError(f"bad filter node {ft.op!r}")

    # ------------------------------------------------------------------
    # traversal (ref query.go:1902 ProcessGraph)
    # ------------------------------------------------------------------

    def _flat_block_eligible(self, i: int, gq: GraphQuery) -> bool:
        """Whether block `i` may take the compiled flat child
        expansion: no variables in or out, no block-level modifiers,
        and every child a plain scalar leaf (or bare `uid`). Pure
        structure + schema, so the plan binds the verdict once per
        (skeleton, epoch); anything this misses (a predicate created
        after compile stays on the interpreter until the next epoch)
        costs only the fast path, never correctness."""
        if (gq.alias == "var" or gq.cascade or gq.normalize
                or gq.ignore_reflex or gq.is_count or gq.is_empty
                or gq.var or gq.facet_var or gq.facets is not None
                or gq.facets_filter is not None):
            return False
        needs, provides = self._block_vars_of(i, gq)
        if needs or provides:
            return False
        if any(o.attr.startswith(("val(", "facet:")) for o in gq.order):
            return False
        if not gq.children:
            return False
        for c in gq.children:
            if (c.expand or c.children or c.var or c.facet_var
                    or c.facets is not None or c.facets_filter is not None
                    or c.filter is not None or c.order or c.is_count
                    or c.math is not None or c.agg_func or c.agg_pred
                    or c.is_internal or c.cascade or c.normalize
                    or c.langs or c.recurse is not None
                    or c.shortest is not None or c.is_groupby
                    or c.checkpwd_pwd is not None or c.is_empty):
                return False
            if c.attr == "uid":
                continue
            if c.attr.startswith(("~", "val(", "fragment/")) \
                    or c.attr == "math":
                return False
            ps = self.db.schema.get(c.attr)
            if ps is None or ps.list_ or ps.value_type == TypeID.UID:
                return False
        return True

    def _expand_children_flat(self, parent: ExecNode,
                              children: list[GraphQuery],
                              src: np.ndarray):
        """Straight-line child expansion for plan-proven flat blocks:
        semantically the scalar tail of _process_child (columnar
        gather, exact posting-walk fallback) with the generic
        dispatch, sibling scheduling and per-child span bookkeeping
        compiled away. The level checkpoint stays — deadlines and the
        chaos failpoint fire exactly like the interpreted path."""
        self._checkpoint(
            f"level {parent.gq.alias or parent.gq.attr}")
        for cgq in children:
            cn = ExecNode(cgq, src=src)
            if cgq.attr != "uid":
                cn.tablet = self._tablet(cgq.attr)
                if cn.tablet is not None:
                    cn.lazy_cols = True
            parent.children.append(cn)

    def _ensure_child_values(self, ch: ExecNode):
        """Materialize a lazily-deferred scalar child for consumers
        that need per-uid values (the dict emitters); the columnar
        JSON emitter never calls this on clean tablets. Reads the same
        read_ts snapshot the eager path would have — MVCC makes the
        deferral invisible."""
        if not ch.lazy_cols:
            return
        ch.lazy_cols = False
        tab, src = ch.tablet, ch.src
        cv = self._colvals_for_emit(tab, ch.gq, src)
        if cv is not None:
            ch.col_vals = cv
            return
        if hasattr(tab, "prefetch_postings"):
            tab.prefetch_postings(src)
        get = tab.get_postings
        for u in src.tolist():
            ps = get(u, self.read_ts)
            if ps:
                ch.values[u] = ps

    def _expand_children(self, parent: ExecNode,
                         children: list[GraphQuery], src: np.ndarray):
        with _span("expand", level=parent.gq.alias or parent.gq.attr,
                   n=len(src)):
            self._expand_children_inner(parent, children, src)

    def _expand_children_inner(self, parent: ExecNode,
                               children: list[GraphQuery],
                               src: np.ndarray):
        # one traversal level (incl. @cascade recursion into subtrees)
        self._checkpoint(f"level {parent.gq.alias or parent.gq.attr}")
        children = self._expand_expand(children, src)
        # dependency-ordered processing: a child consuming a var that a
        # SIBLING subtree binds (facet var, deeper value var) must run
        # after that sibling regardless of listing order — emission
        # keeps the listed order. Unresolvable needs fall back to the
        # listed order (outer blocks / genuinely-undefined vars).
        nodes: dict[int, ExecNode] = {}
        prev_sib = getattr(self, "_sibling_nodes", None)
        self._sibling_nodes = nodes
        try:
            pending = list(enumerate(children))
            while pending:
                progressed = False
                for i, cgq in list(pending):
                    unmet = [vc.name for vc in self._all_needs(cgq)
                             if not self._var_defined(vc.name)
                             and vc.name
                             in getattr(self, "_block_vars", ())]
                    if not unmet:
                        pending.remove((i, cgq))
                        nodes[i] = self._process_child(cgq, src)
                        progressed = True
                if not progressed:
                    for i, cgq in pending:
                        nodes[i] = self._process_child(cgq, src)
                    break
        finally:
            self._sibling_nodes = prev_sib
        for i in range(len(children)):
            parent.children.append(nodes[i])

    def _expand_ownership_guard(self, pname: str) -> None:
        """Ownership check at expansion time: a predicate reached only
        via expand() never appears in the query text, so the server's
        _misroute_guard_query screen cannot see it — without this
        hook, a stale-routed expand racing a tablet cutover silently
        under-reports the moved predicate's edges for the one
        in-flight query (the router's next map fetch routes
        correctly). Same typed failure as the server guard:
        TabletMisrouted carries the forwarding hint. Zero-cost until
        this engine has actually moved a tablet out or holds a split
        hash range."""
        moved = self.db.moved_out
        split = self.db.split_partial
        if not moved and not split:
            return
        if pname in moved and pname not in self.db.tablets:
            from dgraph_tpu.cluster.errors import TabletMisrouted
            raise TabletMisrouted(pname, moved[pname])
        if pname in split:
            from dgraph_tpu.cluster.errors import TabletMisrouted
            raise TabletMisrouted(
                pname, None,
                f"tablet {pname!r} is split across groups; refresh "
                "the tablet map and fan out per sub-tablet")

    def _expand_expand(self, children: list[GraphQuery],
                       src: np.ndarray,
                       keep_uid_leaves: bool = False
                       ) -> list[GraphQuery]:
        """expand(_all_) / expand(Type) (ref query.go:1812
        expandSubgraph). `keep_uid_leaves` is the @recurse mode: the
        recursion traverses expanded uid predicates itself, so they
        stay even without a nested block."""
        out = []
        for c in children:
            if not c.expand:
                out.append(c)
                continue
            preds: list[str] = []
            if c.expand == "_all_":
                type_tab = self._tablet(PREDICATE_TYPE)
                tnames = set()
                if type_tab is not None:
                    for u in src.tolist():
                        for p in type_tab.get_postings(u, self.read_ts):
                            tnames.add(str(p.value.value))
                for tn in sorted(tnames):
                    td = self.db.schema.get_type(tn)
                    if td:
                        preds.extend(td.fields)
                if not tnames:  # no type system in play: expand schema
                    preds = [p for p in self.db.schema.predicates()
                             if not p.startswith("dgraph.")]
            else:
                for tname in c.expand.split(","):
                    td = self.db.schema.get_type(tname)
                    if td:
                        preds.extend(td.fields)
            seen = set()
            for pname in preds:
                if pname in seen:
                    continue
                seen.add(pname)
                self._expand_ownership_guard(pname)
                sub = GraphQuery(attr=pname, children=list(c.children),
                                 filter=c.filter)
                tab = self.db.tablets.get(pname)
                if not c.children and not keep_uid_leaves \
                        and tab is not None \
                        and tab.schema.value_type == TypeID.UID:
                    # expand() without a nested block: expanded UID
                    # predicates emit nothing (ref query4:
                    # TestNestedExpandAll — the innermost expand
                    # yields only scalars; `expand(_all_) { uid }` is
                    # how the suite asks for edge targets)
                    continue
                if c.filter is not None and (
                        tab is None
                        or tab.schema.value_type != TypeID.UID):
                    # expand() @filter filters the expanded EDGES'
                    # targets; scalar predicates have none and drop
                    # out entirely (ref query4_test.go
                    # TestTypeFilterAtExpand: only `owner` survives)
                    continue
                if tab is not None and tab.schema.lang \
                        and tab.schema.value_type != TypeID.UID:
                    # expanded @lang preds emit every language under
                    # attr@lang keys (ref query4_test.go
                    # TestTypeExpandLang: model + model@jp)
                    sub.langs = ["*"]
                out.append(sub)
        return out

    def _process_child(self, gq: GraphQuery, src: np.ndarray) -> ExecNode:
        node = ExecNode(gq, src=src)
        attr = gq.attr
        if attr == "uid" and not gq.is_count:
            # bare `uid` / `x as uid`: binds/emits the enclosing uid set
            if gq.var:
                self.uid_vars[gq.var] = src
            return node
        if gq.is_internal or attr == "math" or gq.agg_func \
                or attr.startswith("val(") or attr.startswith("fragment/"):
            self._process_internal(node)
            return node
        node.reverse = attr.startswith("~")
        if node.reverse:
            attr = attr[1:]
        tab = self._tablet(attr)
        node.tablet = tab
        if tab is None:
            if gq.var:
                self.uid_vars[gq.var] = _EMPTY
            return node
        if node.reverse and not tab.schema.reverse:
            raise GQLError(
                f"reverse edges are not defined for predicate {attr!r} "
                f"(add @reverse to the schema)")
        if tab.schema.value_type == TypeID.UID and not node.reverse or \
                (node.reverse and tab.schema.reverse):
            if gq.is_count and gq.filter is None and not gq.var \
                    and gq.facets_filter is None and not gq.facet_var \
                    and not gq.children \
                    and not hasattr(tab, "prefetch_edges"):
                # count-only child on a LOCAL tablet: per-parent
                # degrees suffice — never materialize (or device-
                # expand) the destination union (ref worker/task.go
                # count tasks read the count index, not the posting
                # lists). Federated proxies keep the edge-prefetch
                # path: their counts ride the level's batched edge
                # cache with zero extra RPCs
                for u in src.tolist():
                    node.counts[u] = self._child_count(
                        tab, u, node.reverse)
                return node
            if hasattr(tab, "prefetch_edges"):
                # federated tablet: one batched task RPC warms every
                # per-parent edge read this block (and its emission)
                # will do (ref worker/task.go per-attr task batching)
                tab.prefetch_edges(src, node.reverse)
            if hasattr(tab, "prefetch_facets") and (
                    gq.facets_filter is not None or gq.facet_var
                    or (gq.facets is not None and not gq.first
                        and not gq.offset and not gq.after)
                    or any(o.attr.startswith("facet:")
                           for o in (gq.order or ()))):
                # federated: one facets RPC per (predicate, level) for
                # the consumers that must see EVERY edge's facets
                # (filters, facet vars, facet ordering) — edges are
                # already batch-cached above, so assembling the
                # level's pairs costs no extra round trips (ref
                # worker/task.go FacetParams on the per-attr task).
                # Plain @facets emission prefetches per parent at the
                # emit site instead, after pagination.
                pairs = []
                for u in src.tolist():
                    dsts = (tab.get_reverse_uids(u, self.read_ts)
                            if node.reverse
                            else tab.get_dst_uids(u, self.read_ts))
                    if node.reverse:
                        pairs.extend((int(d), int(u))
                                     for d in dsts.tolist())
                    else:
                        pairs.extend((int(u), int(d))
                                     for d in dsts.tolist())
                tab.prefetch_facets(pairs)
            # one per-parent edge pass serves both the dest union and
            # every facet-var binding (avoids re-walking high-fanout
            # edge lists once per facet key)
            edge_dsts: dict[int, np.ndarray] | None = None
            if gq.facets_filter is not None or gq.facet_var:
                edge_dsts = {}
                for u in src.tolist():
                    if gq.facets_filter is not None:
                        # @facets(eq(k, v)) drops EDGES, so the union
                        # must be built per-parent (ref worker/
                        # task.go:1806 applyFacetsTree, also edge-wise)
                        dsts = self._edge_dsts_facet_filtered(
                            tab, int(u), node.reverse, gq.facets_filter)
                    else:
                        dsts = (tab.get_reverse_uids(u, self.read_ts)
                                if node.reverse
                                else tab.get_dst_uids(u, self.read_ts))
                    edge_dsts[int(u)] = dsts
            if gq.facets_filter is not None:
                parts = [d for d in edge_dsts.values() if len(d)]
                dest = np.unique(np.concatenate(parts)) if parts \
                    else _EMPTY.copy()
            else:
                dest = self._expand_level(tab, src, node.reverse)
            if gq.filter is not None:
                dest = self._eval_filter(gq.filter, dest)
            node.dest = dest
            if gq.facet_var:
                self._bind_facet_vars(tab, src, node.reverse, gq,
                                      edge_dsts)
            if gq.var:
                if gq.first is not None or gq.offset or gq.after:
                    # `L as friend(first:2, orderasc: dob)`: the var
                    # holds the PAGINATED per-parent edge windows, not
                    # the full expansion (ref query0:
                    # TestUseVarsMultiOrder). Order alone never
                    # changes the union — only a cut window does.
                    parts = []
                    get = tab.get_reverse_uids if node.reverse \
                        else tab.get_dst_uids
                    facet_orders = [o for o in gq.order
                                    if o.attr.startswith("facet:")]
                    for u in src.tolist():
                        # facet-filtered edges were already computed;
                        # a raw re-read would resurrect excluded edges
                        dsts = edge_dsts[int(u)] \
                            if edge_dsts is not None \
                            else get(u, self.read_ts)
                        dsts = _intersect(dsts, dest) \
                            if len(dest) else _EMPTY
                        if not len(dsts):
                            continue
                        if facet_orders:
                            dsts = self._order_paginate_facets(
                                gq, tab, int(u), node.reverse, dsts,
                                facet_orders)
                        else:
                            dsts = self._order_paginate(gq, dsts)
                        if len(dsts):
                            parts.append(np.asarray(dsts,
                                                    dtype=np.uint64))
                    self.uid_vars[gq.var] = np.unique(
                        np.concatenate(parts)) if parts else _EMPTY
                else:
                    self.uid_vars[gq.var] = dest
            if gq.is_count:
                if gq.filter is not None:
                    # count(pred @filter(...)): per-parent size of the
                    # edge list INTERSECTED with the filtered union
                    # (ref TestQueryEmptyRoomsWithTermIndex)
                    get = tab.get_reverse_uids if node.reverse \
                        else tab.get_dst_uids
                    for u in src.tolist():
                        node.counts[u] = len(_intersect(
                            get(u, self.read_ts), dest))
                else:
                    if hasattr(tab, "prefetch_counts"):
                        tab.prefetch_counts(src, node.reverse)
                    for u in src.tolist():
                        node.counts[u] = self._child_count(
                            tab, u, node.reverse)
                if gq.var:
                    # `s as count(friend)` binds a per-parent value
                    # var, zero for parents with no edges (ref
                    # query0_test.go TestQueryVarValAggOrderDesc: the
                    # friendless uid still carries count 0)
                    self.value_vars[gq.var] = {
                        int(u): Val(TypeID.INT, node.counts.get(u, 0))
                        for u in src.tolist()}
            elif gq.is_groupby:
                # emission groups per parent; var assignment aggregates
                # over the whole block's edge set now so later blocks
                # can consume it
                self._bind_groupby_vars(gq, dest)
            else:
                self._expand_children(node, gq.children, dest)
        else:
            # scalar predicate: fetch values for src uids. A pure
            # var-binding block (var(func: ...) { v as pred }) never
            # emits, so the columnar fast path below can skip this
            # per-uid posting walk entirely — at the 21M regime this
            # loop dominates var-heavy aggregation queries (q020)
            if self._bind_var_columnar(node, gq, tab, src):
                return node
            if self._bind_var_emit_columnar(node, gq, tab, src):
                return node
            cv = self._colvals_for_emit(tab, gq, src)
            if cv is not None:
                # columnar emission: json-ready values gathered in one
                # pass — the per-uid get_postings walk below was the
                # bulk of flat-block emission at 21M (q003)
                node.col_vals = cv
                return node
            if hasattr(tab, "prefetch_postings"):
                tab.prefetch_postings(src)
            for u in src.tolist():
                ps = tab.get_postings(u, self.read_ts)
                if ps:
                    node.values[u] = ps
            if gq.is_count:
                for u in src.tolist():
                    node.counts[u] = len(node.values.get(u, ()))
            if gq.var:
                vmap = {}
                for u, ps in node.values.items():
                    sel = self._select_posting(ps, gq.langs)
                    if sel is not None:
                        vmap[u] = self._typed(tab, sel)
                self.value_vars[gq.var] = vmap
            if gq.facet_var:
                for key, varname in gq.facet_var.items():
                    vmap = {}
                    for u, ps in node.values.items():
                        sel = self._select_posting(ps, gq.langs)
                        if sel is not None and key in sel.facets:
                            vmap[u] = sel.facets[key]
                    self.value_vars[varname] = vmap
        return node

    def _colvals_for_emit(self, tab, gq, src: np.ndarray
                          ) -> Optional[dict]:
        """uid -> json-ready value for a FLAT scalar child (no langs,
        lists, facets, counts or var binding), gathered through the
        cached column view — replaces the per-uid posting walk both at
        process time and inside _emit_uid/_emit_value.  None keeps the
        exact path."""
        if gq.langs or gq.is_count or gq.var or gq.facet_var \
                or gq.facets is not None or gq.facets_filter is not None \
                or gq.children or tab.schema.list_:
            return None
        colview = self._colview(tab)
        if colview is None:
            return None
        srcs, tid, data, enc = colview
        pos, hit = _col_positions(srcs, src)
        sel = pos[hit]
        uids = src[hit].tolist()
        if data is not None:
            if tid == TypeID.BOOL:
                vals = [bool(v) for v in data[sel].tolist()]
            else:
                vals = data[sel].tolist()
        else:
            # STRING/DEFAULT/DATETIME columns carry the exact
            # to_json_value payload (isoformat for datetimes)
            dec = colview.decoded()
            vals = [dec[j] for j in sel.tolist()]
        return dict(zip(uids, vals))

    def _bind_var_columnar(self, node: ExecNode, gq, tab,
                           src: np.ndarray) -> bool:
        """Vectorized value-var binding over the clean tablet's column
        view: one searchsorted + array gather instead of a per-uid
        get_postings loop. Only for blocks whose values are consumed
        EXCLUSIVELY through the var (nothing emits, counts, or reads
        facets), with untagged single values — everything else keeps
        the exact posting path."""
        if not gq.var or gq.langs or gq.is_count or gq.facet_var \
                or gq.children or gq.facets is not None \
                or getattr(self, "_block_emits", True):
            return False
        colview = self._colview(tab)
        if colview is None or len(colview.extra_srcs) \
                or colview.tid == TypeID.DATETIME:
            # lang-tagged postings need _select_posting semantics; a
            # DATETIME column caches ISO strings but the var needs the
            # datetime value — both keep the per-posting walk
            return False
        srcs, tid, data, enc = colview
        pos, hit = _col_positions(srcs, src)
        sel = pos[hit]
        inc_counter("query_columnar_var_bind_total")
        if data is not None:
            # numeric var (data arrays exist only for INT/FLOAT/BOOL):
            # stays columnar END-TO-END — math, agg, val() filters and
            # order keys consume the arrays; a dict materializes only
            # if a legacy consumer asks
            self.value_vars[gq.var] = make_colvar(src[hit], data[sel],
                                                  tid)
        else:
            dec = colview.decoded()
            self.value_vars[gq.var] = {
                u: Val(tid, dec[j])
                for u, j in zip(src[hit].tolist(), sel.tolist())}
        return True

    def _bind_var_emit_columnar(self, node: ExecNode, gq, tab,
                                src: np.ndarray) -> bool:
        """Emitting block that ALSO binds a var (d as pred): serve the
        emission from the column view AND bind the var columnarly —
        datetime vars carry (float epoch seconds, exact objects) so
        math/since() stays vectorized (ref query/math.go:213,
        aggregator.go applySince) while materialization stays exact.
        The q046 shape walked 1M postings per query otherwise."""
        if not gq.var or gq.langs or gq.is_count or gq.facet_var \
                or gq.children or gq.facets is not None \
                or tab.schema.list_:
            return False
        colview = self._colview(tab)
        if colview is None or len(colview.extra_srcs):
            return False
        srcs, tid, data, enc = colview
        pos, hit = _col_positions(srcs, src)
        sel = pos[hit]
        bound = src[hit]
        if data is not None:
            vmap = make_colvar(bound, data[sel], tid)
            if vmap is None:
                return False
            if tid == TypeID.BOOL:
                vals = [bool(v) for v in data[sel].tolist()]
            else:
                vals = data[sel].tolist()
        elif tid == TypeID.DATETIME and colview.dt_secs is not None:
            vmap = ColVar(bound, colview.dt_secs[sel], TypeID.DATETIME,
                          objs=colview.dt_objs[sel])
            dec = colview.decoded()
            vals = [dec[j] for j in sel.tolist()]
        elif tid in (TypeID.STRING, TypeID.DEFAULT):
            dec = colview.decoded()
            vals = [dec[j] for j in sel.tolist()]
            vmap = {u: Val(tid, v)
                    for u, v in zip(bound.tolist(), vals)}
        else:
            return False
        inc_counter("query_columnar_var_bind_total")
        self.value_vars[gq.var] = vmap
        node.col_vals = dict(zip(bound.tolist(), vals))
        return True

    # -- facets (ref worker/task.go:1806 applyFacetsTree,
    #    types/facets/utils.go:129) --

    def _edge_dsts_facet_filtered(self, tab: Tablet, u: int,
                                  reverse: bool, ft) -> np.ndarray:
        dsts = (tab.get_reverse_uids(u, self.read_ts) if reverse
                else tab.get_dst_uids(u, self.read_ts))
        if not len(dsts):
            return dsts
        keep = []
        for d in dsts.tolist():
            fsrc, fdst = (int(d), u) if reverse else (u, int(d))
            if self._eval_facet_tree(
                    ft, tab.get_facets(fsrc, fdst, self.read_ts)):
                keep.append(d)
        return np.asarray(keep, dtype=np.uint64)

    def _eval_facet_tree(self, ft: FilterTree, facets: dict) -> bool:
        """Boolean facet filter over one edge's facet map."""
        if ft.func is not None:
            fn = ft.func
            fv = facets.get(fn.attr)
            if fv is None:
                return False
            if fn.name in ("allofterms", "anyofterms"):
                have = set(str(fv.value).lower().split())
                want = set(" ".join(str(a.value)
                                    for a in fn.args).lower().split())
                return want <= have if fn.name == "allofterms" \
                    else bool(want & have)
            want_raw = fn.args[0].value if fn.args else None
            try:
                want = convert(Val(TypeID.DEFAULT, want_raw), fv.tid).value
            except ValueError:
                return False
            try:
                return _cmp(fn.name, fv.value, want)
            except TypeError:
                return False
        if ft.op == "and":
            return all(self._eval_facet_tree(c, facets)
                       for c in ft.children)
        if ft.op == "or":
            return any(self._eval_facet_tree(c, facets)
                       for c in ft.children)
        if ft.op == "not":
            return not self._eval_facet_tree(ft.children[0], facets)
        raise GQLError(f"bad facet filter node {ft.op!r}")

    def _bind_facet_vars(self, tab: Tablet, src: np.ndarray,
                         reverse: bool, gq: GraphQuery,
                         edge_dsts: dict[int, np.ndarray]):
        """@facets(v as key): dst uid -> facet value; numeric values
        sum over multiple in-edges (ref query.go valueVarAggregation
        over facet vars). `edge_dsts` is the (already facet-filtered)
        per-parent edge map built by _process_child — one edge pass
        binds every key."""
        vmaps: dict[str, dict[int, Val]] = {k: {} for k in gq.facet_var}
        for u in src.tolist():
            for d in edge_dsts.get(int(u), _EMPTY).tolist():
                fsrc, fdst = (int(d), u) if reverse else (u, int(d))
                facets = tab.get_facets(fsrc, fdst, self.read_ts)
                for key in gq.facet_var:
                    fv = facets.get(key)
                    if fv is None:
                        continue
                    vmap = vmaps[key]
                    prev = vmap.get(int(d))
                    if prev is not None and isinstance(
                            fv.value, (int, float)) and isinstance(
                            prev.value, (int, float)) and not isinstance(
                            fv.value, bool):
                        vmap[int(d)] = Val(fv.tid, prev.value + fv.value)
                    else:
                        vmap[int(d)] = fv
        for key, varname in gq.facet_var.items():
            self.value_vars[varname] = vmaps[key]

    def _child_count(self, tab: Tablet, uid: int, reverse: bool) -> int:
        # count_of serves both directions so a federated proxy answers
        # from its batch-prefetched count cache instead of shipping
        # whole reverse edge lists (ref worker/task.go count tasks)
        return tab.count_of(uid, self.read_ts, reverse=reverse)

    def _typed(self, tab: Tablet, p) -> Val:
        t = tab.schema.value_type
        if t == TypeID.DEFAULT:
            return p.value
        try:
            return convert(p.value, t)
        except ValueError:
            return p.value

    def _select_posting(self, ps, langs: list[str]):
        """Language preference list (ref types/valForLang semantics):
        first matching lang wins; '.' means any; no langs -> untagged
        first, else any."""
        if langs:
            for lg in langs:
                if lg == ".":
                    return ps[0]
                if lg == "*":
                    # multi-key expansion happens in the emit paths;
                    # single-posting consumers (var binding, sort
                    # keys) fall back to any-language
                    return ps[0]
                for p in ps:
                    if p.lang == lg:
                        return p
            return None
        for p in ps:
            if not p.lang:
                return p
        return None

    # -- the hot loop: one level of expansion --

    def _expand_level(self, tab: Tablet, src: np.ndarray,
                      reverse: bool) -> np.ndarray:
        dev = None
        if self.db.prefer_device:
            dev = self._device_expand(tab, src, reverse)
        if dev is not None:
            return dev
        return tab.expand_frontier(src, self.read_ts, reverse)

    # host-side cost constants for the device/host tier choice (coarse
    # per-element figures for the vectorized numpy paths; the fixed
    # side of the comparison is the MEASURED dispatch RTT, so only the
    # order of magnitude matters here)
    _HOST_PER_FRONTIER_UID = 2e-7     # prefetched posting fetch per
    #                                   parent (round-5 measured: the
    #                                   q049/q067 host expansions run
    #                                   ~7.5x faster than the old
    #                                   1.5e-6 estimate)
    _HOST_PER_EDGE = 4e-8             # np.unique share per edge
    # measured device-compute/host-compute ratios per dispatch family
    # (round-5 21M run; see _device_worth) — re-measure HERE, the call
    # sites only reference these. That run reached its chip through a
    # remote runtime that is gone; on a locally attached chip these
    # values are unverified (PERF.md, open questions) and stay until a
    # ledger-backed re-measure replaces them.
    _DEVICE_RATIO_ORDER = 0.9         # multisort/count-page ~parity
    _DEVICE_RATIO_RANGE = 0.5         # range-scan mask
    _DEVICE_RATIO_EXPAND = 0.5        # one-shot expand incl. transfer
    _HOST_PER_ORDER_KEY = 2e-7        # columnar key gather + lexsort
    #                                   share per uid (clean tablets
    #                                   read cached sort-key arrays)
    _HOST_PER_RANGE_VAL = 5e-9        # cached-array mask per value

    def _device_worth(self, est_host_seconds: float,
                      device_ratio: float = 0.0) -> bool:
        """Use the device only when the estimated host cost clears the
        measured dispatch round-trip PLUS the device's own compute
        (ref algo/uidlist.go:151's size-ratio strategy pick, applied
        to the host/accelerator boundary). `device_ratio` is the
        measured device-compute/host-compute ratio for the family:
        0 models a device that answers instantly (batched traversal —
        the digest BFS runs 11-14x host), while the round-5 21M run
        measured ~0.95 for the 1M-row multisort/count-page family
        (device_ms - RTT ≈ host_ms) — dispatching those buys nothing
        but the round-trip, so their sites pass ~0.9 and stay host
        until the host estimate dwarfs the RTT. `device_min_edges
        <= 1` forces the tier — the tests' and operators' explicit
        override."""
        if self.db.device_min_edges <= 1:
            return True
        if not self.db.device_is_accelerator():
            # a CPU 'device' backend shares the host's silicon: XLA-CPU
            # dispatches can only lose to the numpy columnar tier
            return False
        margin = est_host_seconds * (1.0 - device_ratio)
        return margin > self.db.device_dispatch_seconds() * 1.25

    def _device_beats(self, host_seconds: float,
                      device_seconds: float) -> bool:
        """_device_worth for a family whose two sides are both
        reckoned before it runs (the traversals: planner.recurse_costs
        and shortest_costs against bitgraph.level_seconds)."""
        return self._device_worth(
            host_seconds,
            device_ratio=min(1.0, device_seconds / host_seconds)
            if host_seconds else 1.0)

    def _device_expand(self, tab: Tablet, src: np.ndarray,
                       reverse: bool = False) -> Optional[np.ndarray]:
        from dgraph_tpu.engine.device_cache import (
            device_adjacency, device_radjacency,
            device_sharded_adjacency, expand_np,
        )

        if len(src) == 0:
            return None
        way = {"dir": "rev" if reverse else "fwd"}
        if self.db.mesh is not None:
            # uid-range-sharded tier first: a predicate too big for one
            # chip expands via shard_map over the mesh (SURVEY §5.7).
            # Capacity, not latency: the cost gate below never blocks
            # this tier — the single-chip/host choice is moot for a
            # tablet that exceeds one chip.
            sadj = device_sharded_adjacency(self.db, tab, self.read_ts,
                                            reverse)
            if sadj is not None:
                from dgraph_tpu.parallel.dist_graph import \
                    expand_sharded_np
                with device_call("query_sharded_expand_total", way,
                                 sink=self.lat,
                                 program="sharded_expand") as dc:
                    return expand_sharded_np(self.db.mesh, sadj, src,
                                             sync=dc.wait)
        store = tab.reverse if reverse else tab.edges
        deg = tab.edge_count(reverse) / max(1, len(store))
        if not self._device_worth(
                len(src) * (self._HOST_PER_FRONTIER_UID
                            + deg * self._HOST_PER_EDGE),
                # the one-shot expand ships src + result across the
                # dispatch boundary; round-5 21M run: q049's lone
                # gated expand paid the RTT for no compute win
                device_ratio=self._DEVICE_RATIO_EXPAND):
            return None
        adj = (device_radjacency if reverse else device_adjacency)(
            self.db, tab, self.read_ts, allow_dirty=True)
        if adj is None:
            return None
        if tab.dirty():
            # overlay-on-device (ref posting/mvcc.go immutable+mutable
            # layer split): the tile answers rows the overlay never
            # touched; overlay-touched frontier uids take the exact
            # host MVCC path, results union
            touched = tab.overlay_srcs(self.read_ts, reverse=reverse)
            if touched:
                mask = np.isin(src, np.fromiter(
                    touched, dtype=np.uint64, count=len(touched)))
                clean, dirty = src[~mask], src[mask]
                parts = []
                if len(clean):
                    with device_call(
                            "query_device_overlay_expand_total", way,
                            sink=self.lat,
                            program="expand_frontier") as dc:
                        parts.append(expand_np(adj, clean,
                                               sync=dc.wait))
                if len(dirty):
                    parts.append(tab.expand_frontier(
                        dirty, self.read_ts, reverse))
                if not parts:
                    return _EMPTY.copy()
                return np.unique(np.concatenate(parts)) \
                    if len(parts) > 1 else parts[0]
        with device_call("query_device_expand_total", way,
                         sink=self.lat,
                         program="expand_frontier") as dc:
            return expand_np(adj, src, sync=dc.wait)

    # ------------------------------------------------------------------
    # internal nodes: uid/count(uid)/val()/aggregations/math
    # ------------------------------------------------------------------

    def _process_internal(self, node: ExecNode):
        gq = node.gq
        if gq.agg_func:
            if not gq.needs_var:
                # max(pred): only valid inside @groupby (ref
                # groupby.go aggregateGroup; elsewhere the reference
                # rejects it)
                raise GQLError(
                    f"aggregation {gq.agg_func}({gq.agg_pred}) is "
                    "only allowed inside @groupby; use "
                    f"{gq.agg_func}(val(var)) here")
            vc = gq.needs_var[0]
            vmap = self.value_vars.get(vc.name, {})
            src = node.src
            if len(src) \
                    and self._agg_per_parent(node, vc.name, vmap):
                # `min(val(x))` (bare or `n as ...`) with x bound in a
                # SIBLING subtree: one aggregate PER PARENT over that
                # parent's reachable x values (ref query.go
                # valueVarAggregation — TestQueryVarValAggNestedFunc*,
                # TestMinMulti, TestMultiLevelAgg shapes). Vars bound
                # elsewhere keep the whole-block scalar below.
                return
            whole = vc.name in getattr(self, "_block_vars", ()) \
                or not len(src)
            # bound by this block's own subtree (facet var, deeper
            # value var, same-level scalar var): the map's domain
            # is already scoped by where it was bound — aggregate
            # it whole, dgraph's flat-variable semantics (ref
            # TestLevelBasedFacetVarAggSum; a same-level var's
            # keys equal this level's src so whole == restricted);
            # an outer-block var restricts to this level's uids
            if isinstance(vmap, ColVar) \
                    and vmap.tid != TypeID.DATETIME:
                arr = vmap.vals if whole else vmap.gather(src)[1]
                agg = _aggregate_col(gq.agg_func, arr, vmap)
            else:
                vals = list(vmap.values()) if whole \
                    else [vmap[u] for u in src.tolist() if u in vmap]
                agg = _aggregate(gq.agg_func, vals)
            if agg is None and gq.agg_func == "sum" and not len(src):
                # sum over an empty var emits 0 in a row-less block
                # (ref query1:TestAggregateRoot5 "sum(val(m))":0.000000)
                agg = Val(TypeID.FLOAT, 0.0)
            node.values[0] = [Agg(gq.agg_func, agg)]
            if gq.var:
                # `minVal as min(val(a))` in an empty block binds a
                # GLOBAL var: key 0, matching the reference's
                # aggregated-var map (query.go empty-block aggregation;
                # TestAggregateRoot4/TestAggregateEmpty1). An empty
                # aggregate still DEFINES the var so downstream blocks
                # schedule (TestAggregateRoot6 expects [], not an
                # undefined-variable error).
                self.value_vars[gq.var] = \
                    {} if agg is None else {0: agg}
        elif gq.math is not None:
            root = getattr(self, "_block_root", None)
            if root is not None and root.func is None \
                    and not root.uids and not root.needs_var:
                # empty blocks (`me()`) may only do math over
                # aggregated (global, key-0) vars (ref edgraph:
                # "Only aggregated variables allowed within empty
                # block." — query1:TestAggregateRootError)
                for vn in _math_tree_vars(gq.math):
                    vmap0 = self.value_vars.get(vn, {})
                    keys = vmap0.uids if isinstance(vmap0, ColVar) \
                        else vmap0.keys()
                    if any(int(k) != 0 for k in keys):
                        raise GQLError(
                            "Only aggregated variables allowed "
                            "within empty block.")
            vmap = _eval_math(gq.math, self.value_vars, node.src)
            if gq.var:
                self.value_vars[gq.var] = vmap
            node.values = _internal_values(vmap, node.src, "math")
        elif gq.attr.startswith("val("):
            vc = gq.needs_var[0]
            vmap = self.value_vars.get(vc.name, {})
            node.values = _internal_values(vmap, node.src, "val")
        elif gq.checkpwd_pwd is not None:
            # checkpwd(pred, "plain") per row (ref query3:
            # TestCheckPassword; worker/task.go handleCheckPassword)
            from dgraph_tpu.models.types import verify_password

            tab = self._tablet(gq.attr)
            for u in node.src.tolist():
                ok = tab is not None and any(
                    verify_password(gq.checkpwd_pwd,
                                    str(p.value.value))
                    for p in tab.get_postings(int(u), self.read_ts))
                node.values[int(u)] = [
                    Agg("checkpwd", Val(TypeID.BOOL, ok))]

    def _agg_per_parent(self, node: ExecNode, name: str,
                        vmap) -> bool:
        """Level-based aggregation (ref query.go valueVarAggregation):
        when the aggregated var is bound inside a sibling subtree of
        the same block, each PARENT uid aggregates over the x values
        reachable through that sibling's edges. Binds the result var
        and per-parent node.values; returns False when no sibling
        chain provides the var (caller keeps whole-block semantics)."""
        sibs = getattr(self, "_sibling_nodes", None)
        if not sibs:
            return False
        chain = None
        for e in sibs.values():
            if e is node:
                continue
            if e.gq.var == name:
                chain = []  # bound on the parent level itself
                break
            if e.tablet is not None \
                    and (e.tablet.schema.value_type == TypeID.UID
                         or e.reverse):
                sub = self._chain_to(e, name)
                if sub is not None:
                    chain = sub
                    break
        if chain is None:
            return False
        gq = node.gq
        out: dict[int, Val] = {}
        for p in node.src.tolist():
            frontier = [int(p)]
            for e in chain:
                nxt: list[int] = []
                get = e.tablet.get_reverse_uids if e.reverse \
                    else e.tablet.get_dst_uids
                dest = e.dest
                for u in frontier:
                    ds = get(u, self.read_ts)
                    if len(dest):
                        ds = _intersect(ds, dest)
                    nxt.extend(int(d) for d in ds.tolist())
                frontier = sorted(set(nxt))
            vals = [vmap[u] for u in frontier if u in vmap]
            agg = _aggregate(gq.agg_func, vals)
            if agg is not None:
                out[int(p)] = agg
                node.values[int(p)] = [Agg(gq.agg_func, agg)]
        if gq.var:
            self.value_vars[gq.var] = out
        return True

    def _chain_to(self, e: ExecNode, name: str):
        """Edge-node path from sibling `e` down to the subtree level
        that binds `name` (scalar var or facet var), or None."""
        if name in e.gq.facet_var.values():
            return [e]
        for c in e.children:
            if c.gq.var == name:
                return [e]
        for c in e.children:
            if c.tablet is not None \
                    and (c.tablet.schema.value_type == TypeID.UID
                         or c.reverse):
                sub = self._chain_to(c, name)
                if sub is not None:
                    return [e] + sub
        return None

    # ------------------------------------------------------------------
    # order + pagination (ref query.go:2231 applyOrderAndPagination)
    # ------------------------------------------------------------------

    def _order_paginate(self, gq: GraphQuery, uids: np.ndarray
                        ) -> np.ndarray:
        if gq.order:
            for o in gq.order:
                if o.attr.startswith("val("):
                    vn = o.attr[4:-1]
                    if vn not in self.value_vars \
                            and vn not in self.uid_vars:
                        # bound later in this same block: the
                        # reference rejects rather than ordering by
                        # a not-yet-computed var (query1:
                        # TestUseVariableBeforeDefinitionError)
                        raise GQLError(
                            f"Variable: [{vn}] used before "
                            "definition.")
                    # ordering by val(v) keeps ONLY uids v is bound
                    # for (ref query0_test.go
                    # TestQueryVarValOrderDescMissing -> empty)
                    vmap = self.value_vars.get(vn, {})
                    uids = _intersect(uids, _var_domain(vmap))
                elif o.attr != "uid" \
                        and not o.attr.startswith("facet:"):
                    oattr = o.attr.lstrip("~")
                    otab = self._tablet(oattr)
                    if otab is None and not self.db.schema.has(oattr):
                        # ref query2:TestToFastJSONOrderNameError —
                        # ordering by a predicate the schema has
                        # never seen is a typo, not an empty sort
                        raise GQLError(
                            f"cannot order by unknown attribute "
                            f"{oattr!r}")
                    if otab is not None and otab.schema.list_:
                        # ref query1:TestMultipleValueSortError
                        raise GQLError(
                            f"Sorting not supported on attr: "
                            f"{o.attr} of type: [scalar]")
                    if otab is not None and \
                            otab.schema.value_type == TypeID.BOOL:
                        # ref query1:TestBoolSort (types.Sort has no
                        # bool ordering)
                        raise GQLError(
                            f"Sorting not supported on attr: "
                            f"{o.attr} of type: bool")
            paged = self._device_order_page(gq, uids)
            if paged is not None:
                return paged
            uids = self._apply_order(gq.order, uids)
        if gq.after:
            if gq.order:
                pos = np.nonzero(uids == gq.after)[0]
                uids = uids[int(pos[0]) + 1:] if len(pos) else uids
            else:
                uids = uids[uids > gq.after]
        off = gq.offset or 0
        if off:
            uids = uids[off:]
        if gq.first is not None:
            if gq.first >= 0:
                uids = uids[: gq.first]
            else:
                uids = uids[gq.first:]
        return uids

    def _apply_order(self, orders, uids: np.ndarray) -> np.ndarray:
        with _span("sort", n=len(uids), keys=len(orders)) as sp:
            return self._apply_order_inner(orders, uids, sp)

    def _apply_order_inner(self, orders, uids: np.ndarray,
                           sp: Optional[dict] = None) -> np.ndarray:
        """Multi-key value sort; stable, missing-value uids last
        (ref types/sort.go:118 + worker/sort.go)."""
        # device_min_edges <= 1 is the explicit force-device override
        # (tests, operators): it outranks the presorted host shortcut
        forced = self.db.prefer_device and self.db.device_min_edges <= 1
        # tier choice: presorted-permutation walk ("columnar") /
        # device multisort / host key-gather + lexsort ("postings").
        # rows_by_tier carries each tier's REAL cost driver — the
        # permutation walk streams the whole column, the lexsort
        # scales with candidates x keys — replacing the static 8x
        # candidate-fraction rule with the cost model.
        dec = tier = None
        info = None
        if not forced and len(uids) and self._adaptive:
            info = self._presorted_info(orders)

            def _build_sort():
                avail = ["postings"]
                rows = {"postings": len(uids) * max(1, len(orders))}
                if info is not None:
                    avail.append("columnar")
                    rows["columnar"] = len(info[1])
                if self.db.prefer_device and len(uids) >= 8 \
                        and self.db.device_is_accelerator():
                    avail.append("device")
                    rows["device"] = len(uids)
                return self._tier_decision(
                    "sort", orders[0].attr,
                    {"estRows": len(uids), "estRowsMax": len(uids),
                     "basis": "exact", "source": "candidate set"},
                    tuple(avail), rows_by_tier=rows)
            dec = self._routed(
                ("sort", orders[0].attr, len(orders),
                 len(uids).bit_length(), info is not None),
                _build_sort)
            tier = dec.tier if dec is not None else None
        if not forced:
            if dec is None:
                fast = self._apply_order_presorted(orders, uids, info)
                if fast is not None:
                    # static path serves the permutation tier too:
                    # stamp it so its cost cells land under "columnar"
                    # (the tier name the planner reads), not the
                    # observer's default "host"
                    if sp is not None:
                        sp["tier"] = "columnar"
                    return fast
            elif tier == "columnar":
                # the decision already weighed candidate-vs-column
                # size: skip the static 8x fraction rule
                fast = self._apply_order_presorted(
                    orders, uids, info, ignore_size_rule=True)
                if fast is not None:
                    self._record_outcome(dec, len(uids))
                    if sp is not None:
                        sp["tier"] = "columnar"
                    return fast
        if (tier == "device") if dec is not None else (
                self.db.prefer_device and len(uids) >= 8
                and self._device_worth(
                    len(uids) * len(orders) * self._HOST_PER_ORDER_KEY,
                    device_ratio=self._DEVICE_RATIO_ORDER)):
            dev = self._device_apply_order(orders, uids)
            if dev is not None:
                self._record_outcome(dec, len(uids))
                if sp is not None:
                    sp["tier"] = "device"
                return dev
        if forced:
            fast = self._apply_order_presorted(orders, uids)
            if fast is not None:
                if sp is not None:
                    sp["tier"] = "columnar"
                return fast
        self._record_outcome(dec, len(uids))
        if sp is not None:
            sp["tier"] = "postings"
        keyrows = [self._order_key_cols(o, uids) for o in orders]
        # lexsort: last key is primary
        cols = []
        for col, sub in reversed(keyrows):
            cols.append(sub)
            cols.append(col)  # missing flag dominates its key
        cols.insert(0, uids)  # final tiebreak: uid asc
        order = np.lexsort(tuple(cols))
        return uids[order]

    def _presorted_info(self, orders):
        """(tablet, sorted-column uids) when the presorted-permutation
        sort tier is structurally available for this order spec —
        single key, columnar on, clean tablet with a cached
        permutation — else None. Shared by the static fast path and
        the planner's availability probe so the two can never
        diverge."""
        if len(orders) != 1 or not self._columnar_on():
            return None
        o = orders[0]
        if o.attr == "uid" or o.attr.startswith(("val(", "facet:")) \
                or o.lang in (".", "*"):
            return None
        tab = self._tablet(o.attr)
        if tab is None or not hasattr(tab, "sorted_by_key_uids") \
                or tab.dirty() or self.read_ts < tab.base_ts:
            return None
        suids, _skeys = tab.sort_key_arrays(o.lang or "")
        if not len(suids):
            return None
        return tab, suids

    def _apply_order_presorted(self, orders, uids: np.ndarray,
                               info=None, ignore_size_rule: bool = False
                               ) -> Optional[np.ndarray]:
        """Single-key order-by through the tablet's CACHED
        (key, uid)-sorted permutation: one membership gather over the
        pre-sorted column replaces the per-query key gather + lexsort
        — worker/sort.go walks the value-ordered index the same way.
        Only when the candidate set is a sizable fraction of the
        column (streaming a 1M-row permutation to order 50 uids would
        lose) unless the planner's cost model already decided
        (ignore_size_rule); missing-key uids append uid-ascending,
        identical to the lexsort's missing-flag column."""
        if info is None:
            info = self._presorted_info(orders)
        if info is None:
            return None
        tab, suids = info
        o = orders[0]
        if not ignore_size_rule and len(uids) * 8 < len(suids):
            return None
        op, attr = tab.sorted_by_key_uids(o.lang or "", bool(o.desc))
        from dgraph_tpu.engine.device_cache import host_column_tile
        host_column_tile(self.db, tab, attr, op)
        full, perm = op.uids, op.perm
        inc_counter("query_order_presorted_total")
        # probe in the SMALLER direction (candidates into the sorted
        # column), then re-order the hit mask through the permutation
        pos, hit = _col_positions(suids, uids)
        mask = np.zeros(len(suids), bool)
        mask[pos[hit]] = True
        ordered = full[mask[perm]]
        if len(ordered) == len(uids):
            return ordered
        rest = uids[~hit]  # no sort key: appended uid-ascending
        return np.concatenate([ordered, rest])

    def _order_device_views(self, orders) -> Optional[list]:
        """DeviceValues views for every order key, or None when any
        key has no device view (val()/facet orders, dirty/small
        tablets)."""
        from dgraph_tpu.engine.device_cache import device_values

        dvs = []
        for o in orders:
            if o.attr.startswith("val(") or o.attr.startswith("facet:"):
                return None
            tab = self._tablet(o.attr)
            if tab is None or not hasattr(tab, "sort_key_pairs"):
                return None
            dv = device_values(self.db, tab, self.read_ts, o.lang)
            if dv is None:
                return None
            dvs.append(dv)
        return dvs

    def _device_apply_order(self, orders, uids: np.ndarray
                            ) -> Optional[np.ndarray]:
        """Whole multi-key (and lang-tagged) order-by on device: one
        multisort call over per-attr DeviceValues rank columns (ref
        worker/sort.go:300 multiSort). Falls back to the host lexsort
        whenever any order key has no device view (val() orders,
        dirty/small tablets, >32-bit uids)."""
        from dgraph_tpu.ops.graph import multisort
        from dgraph_tpu.ops.uidvec import SENTINEL, pad_to, to_numpy

        if np.any(uids > 0xFFFFFFFE):
            return None
        dvs = self._order_device_views(orders)
        if dvs is None:
            return None
        import jax.numpy as jnp
        with device_call("query_device_multisort_total", sink=self.lat,
                         program="multisort") as dc:
            cand = np.full(pad_to(len(uids)), SENTINEL, np.uint32)
            cand[: len(uids)] = np.sort(uids).astype(np.uint32)
            out = multisort(jnp.asarray(cand),
                            tuple(dv.uids for dv in dvs),
                            tuple(dv.ranks for dv in dvs),
                            tuple(bool(o.desc) for o in orders))
            res = to_numpy(dc.wait(out))
        return res[: len(uids)].astype(np.uint64)

    _PAGE_MAX_FIRST = 2048

    def _page_window(self, first: int) -> int:
        w = 8
        while w < first:
            w <<= 1
        return w

    def _device_resident_root(self, gq: GraphQuery, uids: np.ndarray,
                              allow_filter: bool = False):
        """The device-resident uid vector of an unfiltered clean
        has(attr) root, or None. When the root candidate set IS the
        tablet's own device view, the sort page kernel reads it in
        place — no 4MB-per-query upload across the host link.
        `allow_filter` is the fused-path relaxation: fusion calls this
        with the PRE-filter root (its kernel applies the filter as
        membership masks), so a filter's presence no longer disproves
        uids == the tablet's key set."""
        from dgraph_tpu.engine.device_cache import (
            device_adjacency, device_values,
        )

        fn = gq.func
        if fn is None or fn.name != "has" or fn.attr.startswith("~") \
                or (gq.filter is not None and not allow_filter) \
                or gq.uids or gq.needs_var:
            return None
        tab = self.db.tablets.get(fn.attr)
        if tab is None or not hasattr(tab, "schema"):
            return None
        if getattr(tab, "is_uid", False):
            adj = device_adjacency(self.db, tab, self.read_ts)
            if adj is not None and adj.n_src == len(uids):
                return adj.src_uids
            return None
        dv = device_values(self.db, tab, self.read_ts)
        if dv is not None and dv.n == len(uids):
            return dv.uids
        return None

    def _device_order_page(self, gq: GraphQuery, uids: np.ndarray
                           ) -> Optional[np.ndarray]:
        """order + after + offset + first fused into ONE device
        dispatch returning only the page (ref worker/sort.go:177
        processSort applies offset+count inside the sort). The full
        multisort path transfers the whole candidate vector both ways
        (~8MB at the 21M regime); this moves a few KB."""
        first = gq.first
        if first is None or first <= 0 or first > self._PAGE_MAX_FIRST:
            return None
        if not 0 <= (gq.offset or 0) <= 2**30 \
                or (gq.after or 0) > 0xFFFFFFFE:
            # the kernels compute start in int32: an absurd offset
            # must take the host path, not wrap the slice start
            return None
        if not self.db.prefer_device or len(uids) < 8:
            return None
        if not self._device_worth(
                len(uids) * len(gq.order) * self._HOST_PER_ORDER_KEY,
                device_ratio=self._DEVICE_RATIO_ORDER):
            return None
        if np.any(uids > 0xFFFFFFFE):
            return None
        dvs = self._order_device_views(gq.order)
        if dvs is None:
            return None
        from dgraph_tpu.ops.graph import multisort_page
        from dgraph_tpu.ops.uidvec import SENTINEL, pad_to, to_numpy
        import jax.numpy as jnp

        cand = self._device_resident_root(gq, uids)
        with device_call("query_device_sort_page_total", sink=self.lat,
                         program="multisort_page") as dc:
            if cand is None:
                buf = np.full(pad_to(len(uids)), SENTINEL, np.uint32)
                buf[: len(uids)] = np.sort(uids).astype(np.uint32)
                cand = jnp.asarray(buf)
            out = multisort_page(
                cand,
                tuple(dv.uids for dv in dvs),
                tuple(dv.ranks for dv in dvs),
                tuple(bool(o.desc) for o in gq.order),
                self._page_window(first),
                jnp.uint32(gq.after or 0),
                jnp.int32(gq.offset or 0))
            res = to_numpy(dc.wait(out))
        start = int(np.int32(res[-1]))
        valid = max(0, min(first, len(uids) - start))
        return res[:valid].astype(np.uint64)

    def _fused_spec(self, gq: GraphQuery, i: int):
        """Structural whole-plan-fusion verdict for block `i`,
        recomputed per request — deliberately NOT memoized on the
        plan: the verdict carries this request's filter Function
        objects, and the plan is shared across requests whose literals
        differ (a cached leaf would replay the FIRST request's
        literals into every later mask — wrong bytes, not just wrong
        speed). The walk is a handful of attribute checks and schema
        probes, noise next to one device dispatch. None on the
        interpreted path — fusion is a compiled-plan tier."""
        if self.plan is None or i < 0:
            return None
        from dgraph_tpu.query import fusion
        return fusion.block_eligible(gq, self.db.schema)

    def _fused_block_page(self, gq: GraphQuery, fspec, root: np.ndarray,
                          node: ExecNode) -> Optional[np.ndarray]:
        """Whole-block chain — filter set algebra + multi-key order +
        after/offset/first — as ONE fused device dispatch
        (query/fusion.py), or None to run the staged pipeline.
        `root` is the staged `_root_uids` result: the index probes
        stay on host (planner/tier machinery intact) and fusion
        collapses everything downstream of them. Every fallback stamps
        its reason on the node ("staged:<why>") so EXPLAIN attributes
        the block either way; byte-parity with the staged path is the
        structural contract (tests/test_columnar_parity.py)."""
        why, fs = fspec
        if why != "ok":
            node.fused = "staged:" + why
            return None

        def _stage(reason: str) -> None:
            node.fused = "staged:" + reason
            return None

        if not getattr(self.db, "prefer_fused", True):
            return _stage("disabled")
        first = gq.first
        if first is None or first <= 0 or first > self._PAGE_MAX_FIRST:
            return _stage("first-range")
        if gq.after:
            # the selection kernel can't bound how deep an arbitrary
            # cursor uid sits in the ordering
            return _stage("after-cursor")
        window = self._page_window(first)
        offset = gq.offset or 0
        from dgraph_tpu.ops.graph import FUSED_SEL_CAP
        if not 0 <= offset or offset + window > FUSED_SEL_CAP:
            # the page must fit inside the kernel's static survivor cap
            return _stage("deep-offset")
        if len(root) < max(8, getattr(self.db, "fused_min_rows", 1024)):
            # tiny roots: one dispatch still costs a round-trip the
            # host pipeline finishes first
            return _stage("small-root")
        if np.any(root > 0xFFFFFFFE):
            return _stage("uids-64bit")
        dvs = self._order_device_views(gq.order)
        if dvs is None:
            # dirty/small/unexported order tablets: the same MVCC rule
            # as every device tier
            return _stage("no-device-views")

        from dgraph_tpu.engine.device_cache import device_values
        from dgraph_tpu.ops.uidvec import SENTINEL, pad_to, to_numpy
        from dgraph_tpu.query import fusion
        import jax.numpy as jnp

        from dgraph_tpu.ops.graph import dv_view

        # root fingerprint: the snapshot ts plus cheap positional
        # invariants of the root set. Memo keys below pair it with the
        # full leaf/func signature, so a hit requires the same literals
        # against the same snapshot — the conditions under which the
        # staged chain would reproduce the same bytes.
        rfp = (self.read_ts, len(root),
               int(root[0]) if len(root) else 0,
               int(root[-1]) if len(root) else 0,
               int(root[::257].sum()) if len(root) else 0)
        cand = self._device_resident_root(gq, root, allow_filter=True)
        host_root = None
        if cand is None:
            def _root_upload():
                hr = np.sort(root).astype(np.uint32)
                buf = np.full(pad_to(len(root)), SENTINEL, np.uint32)
                buf[: len(hr)] = hr
                return hr, jnp.asarray(buf)

            host_root, cand = self.plan.memo(
                ("fused-root", self._fn_sig(gq.func), rfp),
                _root_upload)

        fop, leaves = fs
        rank_views, rank_luts, rank_los, rank_his, rank_negs = \
            [], [], [], [], []
        fparts, set_negs = [], []
        for fn, neg, kind in leaves:
            bounds = None
            if kind == "rank":
                tab = self._tablet(fn.attr)
                dv = device_values(self.db, tab, self.read_ts) \
                    if tab is not None else None
                if dv is not None:
                    bounds = self._rank_leaf_bounds(dv, tab.schema, fn)
            if bounds is not None:
                view, is_lut = dv_view(dv)
                rank_views.append(view)
                rank_luts.append(is_lut)
                rank_los.append(bounds[0])
                rank_his.append(bounds[1])
                rank_negs.append(bool(neg))
                continue
            # set form — host root-context probe (pointwise-equal to
            # the staged candidate-context eval, the parity
            # precondition block_eligible enforces), and the demotion
            # target when a rank leaf's view is missing (dirty/small
            # tablet) or its literal doesn't convert (the staged eval
            # then raises the identical GQLError)
            sig = self._fn_sig(fn)

            def _leaf(fn=fn):
                return self._eval_func(fn, None)

            if host_root is not None:
                # host-known candidates: fold the membership test into
                # ONE host searchsorted and ship a cand-ALIGNED bool
                # mask — the kernel sees a pure vector operand instead
                # of a device-side binary search per candidate
                def _mask(fn=fn, sig=sig, cand=cand, hr=host_root):
                    part = self.plan.memo(
                        ("fused-leaf", sig, self.read_ts), _leaf) \
                        if sig is not None else _leaf()
                    mask = np.zeros(int(cand.shape[0]), bool)
                    if len(part) and len(hr):
                        pi = np.minimum(np.searchsorted(part, hr),
                                        len(part) - 1)
                        mask[: len(hr)] = part[pi] == hr
                    return jnp.asarray(mask)

                fparts.append(
                    self.plan.memo(("fused-mask", sig, rfp), _mask)
                    if sig is not None else _mask())
            else:
                part = self.plan.memo(
                    ("fused-leaf", sig, self.read_ts), _leaf) \
                    if sig is not None else _leaf()
                if np.any(part > 0xFFFFFFFE):
                    return _stage("filter-64bit")

                def _part_upload(part=part):
                    buf = np.full(pad_to(len(part)), SENTINEL,
                                  np.uint32)
                    buf[: len(part)] = part.astype(np.uint32)
                    return jnp.asarray(buf)

                fparts.append(
                    self.plan.memo(("fused-part", sig, self.read_ts),
                                   _part_upload)
                    if sig is not None else _part_upload())
            set_negs.append(bool(neg))
        # primary-rank bucket geometry: static shift (recompiles only
        # when the key domain crosses a power of two), traced recenter
        domain = max(1, len(dvs[0].host_keys))
        shift = max(0, (domain - 1).bit_length() - 12)
        base0 = -(domain - 1) if gq.order[0].desc else 0
        ord_pairs = [dv_view(dv) for dv in dvs]
        run = fusion.fused_executable(
            self.db.mesh, self.plan.mesh_key, fop,
            tuple(rank_negs), tuple(set_negs), host_root is not None,
            tuple(bool(o.desc) for o in gq.order), window, shift,
            tuple(rank_luts), tuple(is_lut for _, is_lut in ord_pairs))
        # operands the plan memoized across requests (root, masks,
        # parts) were uploaded by the request that first needed them;
        # what this call uploads are the traced scalars
        with device_call("query_fused_dispatch_total", sink=self.lat,
                         program=run.__name__) as dc:
            out = run(cand, tuple(rank_views),
                      tuple(jnp.int32(b) for b in rank_los),
                      tuple(jnp.int32(b) for b in rank_his),
                      tuple(fparts),
                      tuple(view for view, _ in ord_pairs),
                      jnp.int32(base0), jnp.int32(offset))
            res = to_numpy(dc.wait(out))
        sel_count = int(res[-2])
        n_kept = int(res[-1])
        if sel_count > FUSED_SEL_CAP:
            # boundary tie mass overflowed the survivor cap (e.g. a
            # few-distinct-values primary order): page unprovable on
            # device, the staged chain is the answer
            return _stage("tie-overflow")
        valid = max(0, min(first, n_kept - offset))
        node.fused = "fused"
        return res[:valid].astype(np.uint64)

    @staticmethod
    def _fn_sig(fn) -> Optional[tuple]:
        """Hashable full-literal signature of a root/filter function,
        or None when the call depends on request-scoped state (value
        variables) that a cross-request memo key cannot capture."""
        if fn is None or fn.needs_var or fn.is_value_var \
                or fn.is_len_var:
            return None
        return (fn.name, fn.attr, fn.lang, fn.is_count,
                tuple((a.value, a.is_value_var, a.is_graphql_var)
                      for a in fn.args),
                tuple(fn.uids))

    @staticmethod
    def _rank_leaf_bounds(dv, ps, fn: Function
                          ) -> Optional[tuple[int, int]]:
        """[lo, hi) rank bounds over dv.host_keys for a rank-form
        filter leaf, or None to demote it to set form. Conversion
        mirrors the staged eq/ineq literal handling (Val DEFAULT ->
        predicate type); sort-key injectivity on the rank-exact types
        makes the range byte-equal to the staged leaf set."""
        from dgraph_tpu.models.types import Val, convert, sort_key

        def key(raw) -> int:
            return sort_key(convert(Val(TypeID.DEFAULT, raw),
                                    ps.value_type))

        hk = dv.host_keys
        try:
            if fn.name == "between":
                return (int(np.searchsorted(hk, key(fn.args[0].value),
                                            "left")),
                        int(np.searchsorted(hk, key(fn.args[1].value),
                                            "right")))
            k = key(fn.args[0].value)
        except (ValueError, TypeError, OverflowError,
                AttributeError):
            return None
        lo, hi = 0, len(hk)
        if fn.name == "eq":
            lo = int(np.searchsorted(hk, k, "left"))
            hi = int(np.searchsorted(hk, k, "right"))
        elif fn.name == "ge":
            lo = int(np.searchsorted(hk, k, "left"))
        elif fn.name == "gt":
            lo = int(np.searchsorted(hk, k, "right"))
        elif fn.name == "le":
            hi = int(np.searchsorted(hk, k, "right"))
        elif fn.name == "lt":
            hi = int(np.searchsorted(hk, k, "left"))
        else:
            return None
        return lo, hi

    @staticmethod
    def _count_cmp_bounds(fn: Function) -> Optional[tuple[int, int]]:
        """count-cmp -> inclusive [lo, hi] degree bounds over has()
        candidates (every candidate has degree >= 1)."""
        hi_max = 2**31 - 1
        try:
            v = int(fn.args[0].value)
        except (ValueError, IndexError):
            return None
        if fn.name == "ge":
            return max(v, 1), hi_max
        if fn.name == "gt":
            return max(v + 1, 1), hi_max
        if fn.name == "le":
            return 1, v
        if fn.name == "lt":
            return 1, v - 1
        if fn.name == "eq":
            return max(v, 1), v
        if fn.name == "between":
            try:
                hi = int(fn.args[1].value)
            except (ValueError, IndexError):
                return None
            return max(v, 1), hi
        return None

    def _device_root_count_page(self, gq: GraphQuery
                                ) -> Optional[np.ndarray]:
        """has(A) root + count(A) filter + order + paginate in ONE
        dispatch over A's resident adjacency (candidates = its src
        vector, degrees aligned): nothing uploaded, only the page
        downloaded (ref worker/task.go:1111 handleCompare over the
        count index + sort.go:177). Engages only for the exact shape
        q010 has; anything else falls back to the general path."""
        ft = gq.filter
        fn = gq.func
        if (ft is None or ft.op or ft.children or ft.func is None
                or fn is None or fn.name != "has"
                or fn.attr.startswith("~") or gq.uids or gq.needs_var
                or not gq.order):
            return None
        cfn = ft.func
        if (not cfn.is_count or cfn.attr != fn.attr
                or cfn.needs_var or cfn.attr.startswith("~")):
            return None
        bounds = self._count_cmp_bounds(cfn)
        if bounds is None:
            return None
        first = gq.first
        if first is None or first <= 0 or first > self._PAGE_MAX_FIRST:
            return None
        if not 0 <= (gq.offset or 0) <= 2**30 \
                or (gq.after or 0) > 0xFFFFFFFE:
            return None
        if not self.db.prefer_device:
            return None
        tab = self.db.tablets.get(fn.attr)
        if tab is None or not getattr(tab, "is_uid", False) \
                or not hasattr(tab, "sort_key_pairs"):
            return None
        from dgraph_tpu.engine.device_cache import device_adjacency
        adj = device_adjacency(self.db, tab, self.read_ts)
        if adj is None:
            return None
        if not self._device_worth(
                adj.n_src * (len(gq.order) + 1)
                * self._HOST_PER_ORDER_KEY,
                device_ratio=self._DEVICE_RATIO_ORDER):
            return None
        dvs = self._order_device_views(gq.order)
        if dvs is None:
            return None
        from dgraph_tpu.ops.graph import count_filter_sort_page
        import jax.numpy as jnp
        from dgraph_tpu.ops.uidvec import to_numpy

        with device_call("query_device_count_page_total", sink=self.lat,
                         program="count_filter_sort_page") as dc:
            out = count_filter_sort_page(
                adj.src_uids, adj.degrees,
                jnp.int32(min(bounds[0], 2**31 - 1)),
                jnp.int32(min(bounds[1], 2**31 - 1)),
                tuple(dv.uids for dv in dvs),
                tuple(dv.ranks for dv in dvs),
                tuple(bool(o.desc) for o in gq.order),
                self._page_window(first),
                jnp.uint32(gq.after or 0),
                jnp.int32(gq.offset or 0))
            res = to_numpy(dc.wait(out))
        start = int(np.int32(res[-2]))
        n_kept = int(res[-1])
        valid = max(0, min(first, n_kept - start))
        return res[:valid].astype(np.uint64)

    def _order_key_cols(self, o, uids: np.ndarray
                        ) -> tuple[np.ndarray, np.ndarray]:
        """(missing_flag, key) int64 columns for one order attr over
        `uids` — the cached (uids, keys) sort arrays answer clean
        untagged/lang-selected predicates in two numpy gathers, so a
        1M-row host order-by stops walking a python dict per uid
        (q006 host path: 3.1s -> columnar). Falls back to the exact
        per-uid dict path for val()/facet keys and dirty tablets."""
        attr = o.attr
        if attr == "uid":
            # order by uid: the key IS the uid (this keeps q070's
            # orderasc:uid off the per-uid dict walk). Sign-bit XOR
            # maps uint64 to int64 order-preservingly so uids >= 2^63
            # sort correctly; uid 0 never exists, so desc negation
            # cannot hit INT64_MIN.
            arr = np.ascontiguousarray(uids, dtype=np.uint64)
            sub = (arr ^ np.uint64(1 << 63)).view(np.int64)
            col = np.zeros(len(arr), np.int64)
            return col, (-sub if o.desc else sub)
        if not attr.startswith(("val(", "facet:")) \
                and o.lang not in (".", "*") and self._columnar_on():
            # '.' / '*' tags resolve "any language" via
            # _select_posting; sort_key_pairs matches tags exactly, so
            # those keep the per-uid path
            tab = self._tablet(attr)
            if tab is not None and hasattr(tab, "sort_key_arrays") \
                    and not tab.dirty() and self.read_ts >= tab.base_ts:
                suids, skeys = tab.sort_key_arrays(o.lang or "")
                arr = np.ascontiguousarray(uids, dtype=np.uint64)
                if len(suids):
                    pos = np.clip(np.searchsorted(suids, arr), 0,
                                  len(suids) - 1)
                    hit = suids[pos] == arr
                    sub = np.where(hit, skeys[pos], 0)
                else:
                    hit = np.zeros(len(arr), bool)
                    sub = np.zeros(len(arr), np.int64)
                col = np.where(hit, 0, 1).astype(np.int64)
                return col, (-sub if o.desc else sub)
        vmap = self._order_keys(attr, o.lang, uids)
        col = np.asarray(
            [vmap.get(int(u), (1, 0))[0] for u in uids], dtype=np.int64)
        sub = np.asarray(
            [vmap.get(int(u), (1, 0))[1] for u in uids], dtype=np.int64)
        return col, (-sub if o.desc else sub)

    def _order_keys(self, attr: str, lang: str, uids) -> dict:
        """uid -> (missing_flag, int64 key)."""
        out = {}
        if attr.startswith("val("):
            vmap = self.value_vars.get(attr[4:-1], {})
            if isinstance(vmap, ColVar):
                sub = vmap.take(np.asarray(uids, np.uint64))
                return {int(u): (0, int(k)) for u, k in
                        zip(sub.uids.tolist(),
                            sub.sort_keys().tolist())}
            for u in uids.tolist():
                v = vmap.get(u)
                if v is not None:
                    try:
                        out[u] = (0, sort_key(v))
                    except ValueError:
                        pass
            return out
        tab = self._tablet(attr)
        if tab is None:
            return out
        if self.db.prefer_device and len(uids) >= 8 \
                and self._device_worth(
                    len(uids) * self._HOST_PER_ORDER_KEY,
                    device_ratio=self._DEVICE_RATIO_ORDER):
            dev = self._device_order_keys(tab, uids, lang)
            if dev is not None:
                return dev
        if hasattr(tab, "prefetch_postings"):
            tab.prefetch_postings(uids)
        for u in uids.tolist():
            ps = tab.get_postings(u, self.read_ts)
            sel = self._select_posting(ps, [lang] if lang else [])
            if sel is None and lang and ps:
                # sorting falls back tag -> untagged -> first (ref
                # posting.List.ValueFor; TestToFastJSONOrderLang)
                sel = self._select_posting(ps, []) or ps[0]
            if sel is not None:
                try:
                    # strict schema-type conversion, matching
                    # sort_key_pairs: an unconvertible value has NO
                    # sort key (missing, sorts last) on every path —
                    # _typed would silently sort the raw value here
                    out[u] = (0, sort_key(tab._converted(sel)))
                except ValueError:
                    pass
        return out

    def _device_order_keys(self, tab: Tablet, uids,
                           lang: str = "") -> Optional[dict]:
        """Sort keys for a uid batch in ONE device gather instead of a
        get_postings loop (SURVEY §2a item 4; ref worker/sort.go:177).
        Parity: device_values indexes each uid's first posting in
        `lang` ("" = untagged), exactly what _select_posting picks on
        the host path. The gather input is pow2-padded so repeated
        sorts share compiled code instead of one XLA program per
        candidate count."""
        from dgraph_tpu.engine.device_cache import device_values
        from dgraph_tpu.ops.graph import RANK_MISSING, key_gather
        from dgraph_tpu.ops.uidvec import SENTINEL, pad_to

        dv = device_values(self.db, tab, self.read_ts, lang)
        if dv is None:
            return None
        import jax.numpy as jnp
        u32 = uids[uids <= 0xFFFFFFFE].astype(np.uint32)
        if not len(u32):
            return {}
        with device_call("query_device_orderkeys_total", sink=self.lat,
                         program="key_gather") as dc:
            cand = np.full(pad_to(len(u32)), SENTINEL, np.uint32)
            cand[: len(u32)] = np.sort(u32)
            ranks = np.asarray(
                dc.wait(key_gather(dv, jnp.asarray(cand))))
        out = {}
        for u, r in zip(cand[: len(u32)].tolist(),
                        ranks[: len(u32)].tolist()):
            if r != RANK_MISSING:
                out[u] = (0, int(r))
        return out

    # ------------------------------------------------------------------
    # recurse (ref query/recurse.go:29)
    # ------------------------------------------------------------------

    def _run_recurse(self, node: ExecNode):
        gq = node.gq
        # depth counts LEVELS including the root: depth 2 expands one
        # edge hop (ref query3_test.go TestRecurseQueryLimitDepth1)
        depth = (gq.recurse.depth or 64) - 1
        t0 = _time.perf_counter_ns()
        try:
            with _span("recurse", depth=depth + 1,
                       roots=int(len(node.dest))) as sp:
                bound = self._recurse_bound(gq)
                if bound is None:
                    sp["tier"] = "host"
                    sp["bound"] = False
                    self._run_recurse_nested(node, depth)
                else:
                    self._run_recurse_bound(node, depth, bound, sp)
                inc_counter("recurse_tier_total",
                            labels={"tier": sp["tier"]})
        finally:
            # the span's own time as a counter: less
            # device_call_ns_total{family="recurse"} it is what a
            # @recurse costs off the chip
            inc_counter("recurse_ns_total",
                        _time.perf_counter_ns() - t0)

    def _recurse_bound(self, gq: GraphQuery) -> Optional[list]:
        """[(child, tablet, reverse)] where the block is a BOUND
        @recurse: nothing of it is emitted (a `var` block), it drops
        visited uids (`loop: false`), and every child is a bare uid
        predicate, read at most through the uid variable on it. Such
        a traversal needs no parent -> children map, only each level's
        reach. None keeps the general path: loops, filters, facets,
        expand(), scalar children, `uid`, nested output."""
        if self._block_emits or gq.recurse.allow_loop or gq.cascade \
                or gq.normalize or gq.ignore_reflex or not gq.children:
            return None
        out = []
        for c in gq.children:
            if (c.is_internal or c.expand or c.filter is not None
                    or c.facets is not None or c.facets_filter is not None
                    or c.facet_var or c.children or c.is_count
                    or c.langs or c.order or c.first is not None
                    or c.offset or c.after or c.agg_func
                    or c.math is not None or c.needs_var):
                return None
            rev = c.attr.startswith("~")
            tab = self._tablet(c.attr[1:] if rev else c.attr)
            if tab is None or tab.schema.value_type != TypeID.UID \
                    or (rev and not tab.schema.reverse):
                return None
            out.append((c, tab, rev))
        return out

    def _count_only_readers(self, name: str, own: GraphQuery) -> bool:
        """Whether every block that reads uid variable `name` (bound in
        block `own`) is `b(func: uid(name)) { count(uid) }` and no
        more: then the variable's SIZE answers the request and its
        uids need not leave the device. Only a served read query (one
        with a Latency) is known to keep its variables to itself: an
        upsert's mutation reads them off the executor afterwards
        (engine/db.py). (Not memoized on the plan: `offset` and
        `after` are parameters of a skeleton.)"""
        if self.lat is None:
            return False
        readers = 0
        for b in self.parsed.queries:
            if b is own:
                if any(vc.name == name for vc in self._all_needs(b)):
                    return False
                continue
            if all(vc.name != name for vc in self._all_needs(b)):
                continue
            readers += 1
            if (b.func is None or b.func.name != "uid"
                    or [vc.name for vc in b.func.needs_var] != [name]
                    or any(vc.name != name for vc in b.needs_var)
                    or b.uids or b.filter is not None or b.order
                    or b.first is not None or b.offset or b.after
                    or b.var or b.cascade or b.normalize
                    or b.ignore_reflex or b.is_groupby
                    or b.recurse is not None or b.shortest is not None
                    or not b.children
                    or any(c.attr != "uid" or not c.is_count or c.var
                           or c.children or c.filter is not None
                           or c.needs_var for c in b.children)):
                return False
        return readers > 0

    def _recurse_device(self, tab: Tablet, rev: bool, roots: np.ndarray,
                        depth: int, want_uids: bool
                        ) -> Optional[tuple]:
        """The whole traversal as ONE device program and ONE
        device_call -> (reached count, reached uids or None, levels
        run, {lanes of the call it rode, batch_wait_us, the chips the
        adjacency is split over, the program's name, the call's
        hub-row tiles streamed and all told}); None where
        the host tier is to answer: the gate says so,
        or the device cannot speak for the traversal (roots over 32
        bits, a dirty tablet or one under device_min_edges, a root
        the adjacency does not know).

        The family's `_device_worth` site. Both sides of the choice
        are reckoned before the traversal runs and never from a
        measured span: the host's cost from the depth, the root set's
        size and the tablet's degree moments (planner.recurse_costs),
        the device's from the levels and the adjacency's layout
        (bitgraph.level_seconds: ONE chip's share of it where the
        engine's mesh splits the rows over several). The tile is
        built only for a traversal the device could win at all: one
        that costs the host more than the in-edges' bytes cost the
        memory of the chips that share them."""
        from dgraph_tpu.engine.device_cache import (
            _MAX_U32, device_bitadjacency, uid_mesh,
        )
        from dgraph_tpu.ops import bitgraph
        from dgraph_tpu.query.planner import recurse_costs
        if int(roots[-1]) > _MAX_U32 or not hasattr(tab, "degree_moments"):
            return None       # (a federated proxy is host-only)
        moments = tab.degree_moments(rev)
        host, levels = recurse_costs(len(roots), depth, *moments)
        worth = functools.partial(self._device_beats, host)
        mesh = uid_mesh(self.db)
        chips = 1 if mesh is None else mesh.shape[bitgraph.SHARD_AXIS]
        if not worth(levels * 4 * moments[1] / chips
                     / bitgraph.DENSE_BYTES_PER_S):
            return None
        badj = device_bitadjacency(self.db, tab, self.read_ts,
                                   transpose=rev, dense=True)
        if badj is None or badj.n_slots == 0 \
                or not worth(levels * bitgraph.level_seconds(badj)):
            return None
        program = "bfs_traverse" if badj.mesh is None \
            else "bfs_traverse_sharded"
        # the traversals in flight over this tile ride one call
        # (devicecall.Rendezvous): this request's block is its own
        # all the same, its wait the time until its call's result
        with device_call("query_device_recurse_total", sink=self.lat,
                         program=program) as dc:
            slots = bitgraph.seed_slots(badj, roots.astype(np.uint32))
            if slots is None:
                return None
            meet = Rendezvous.at(badj, bitgraph.LANES,
                                 family="recurse")
            ride = dc.wait_for(
                lambda: meet.ride(
                    (slots, depth),
                    functools.partial(_launch_traversals, badj),
                    _land_traversals, self.ctx),
                out_bytes=8 + (4 * badj.n_slots if want_uids else 0))
            count, levels, reached, tiles = ride.result
            batch = {"lanes": ride.lanes,
                     "batch_wait_us": ride.waited_ns // 1000,
                     "shards": badj.shards, "program": program,
                     "hub_tiles_streamed": tiles[0], "hub_tiles": tiles[1],
                     "column_levels": tiles[2]}
            dc.note(**batch)
            uids = bitgraph.lane_uids(
                badj, np.asarray(reached), ride.lane).astype(np.uint64) \
                if want_uids else None
            return count, uids, levels, batch

    def _run_recurse_bound(self, node: ExecNode, depth: int, bound: list,
                           sp: dict):
        """A bound @recurse: level-at-a-time on the host tier
        (storage/tablet.bfs_levels, one expand_frontier a predicate a
        level), one device traversal on the device tier. Both give the
        general path's answer: a child's variable holds every uid
        reached through it in 1..depth hops."""
        gq = node.gq
        roots = node.dest
        self._checkpoint(f"recurse {gq.alias or gq.attr}")
        if len(bound) == 1 and len(roots) and self.db.prefer_device:
            cgq, tab, rev = bound[0]
            # the uids leave the device only where a later block reads
            # more of the variable than its size
            want = bool(cgq.var) \
                and not self._count_only_readers(cgq.var, gq)
            got = self._recurse_device(tab, rev, roots, depth, want)
            if got is not None:
                count, uids, levels, batch = got
                sp.update(tier="device", levels_run=levels, reached=count,
                          **batch)
                if cgq.var:
                    self.uid_vars[cgq.var] = uids if want else _EMPTY
                    if not want:
                        self._uid_var_counts[cgq.var] = count
                return
        from dgraph_tpu.storage.tablet import bfs_levels
        expanders = [
            (lambda fr, tab=tab, rev=rev:
             tab.expand_frontier(fr, self.read_ts, rev))
            for _, tab, rev in bound]
        # what each child's edges led to, over every level
        accum = [_EMPTY] * len(bound)
        levels = 0
        for reaches, _ in bfs_levels(expanders, roots, depth):
            levels += 1
            accum = [_union(a, r) for a, r in zip(accum, reaches)]
            self._checkpoint(f"recurse {gq.alias or gq.attr}")
        sp.update(tier="host", levels_run=levels,
                  reached=int(len(self._union_many(accum))))
        for (cgq, _, _), uids in zip(bound, accum):
            if cgq.var:
                self.uid_vars[cgq.var] = uids

    def _run_recurse_nested(self, node: ExecNode, depth: int):
        gq = node.gq
        allow_loop = gq.recurse.allow_loop
        frontier = node.dest
        visited = frontier.copy()
        # uid vars bound inside @recurse accumulate every uid reached
        # via that predicate across ALL levels (ref query3_test.go
        # TestRecurseVariable); seeded empty so a recursion that
        # reaches nothing still DEFINES the var (a consumer block must
        # get [], not an undefined-variable error)
        var_accum: dict[str, np.ndarray] = {
            c.var: _EMPTY for c in gq.children
            if not c.is_internal and c.var}
        for _ in range(depth):
            if not len(frontier):
                break
            self._checkpoint(f"recurse {gq.alias or gq.attr}")
            # expand(_all_)/expand(Type) re-resolves per level against
            # the CURRENT frontier's types (ref TestRecurseExpand)
            preds = [c for c in
                     self._expand_expand(gq.children, frontier,
                                         keep_uid_leaves=True)
                     if not c.is_internal]
            node.recurse_preds.append(preds)
            level: dict[str, dict[int, np.ndarray]] = {}
            nxt = _EMPTY
            for cgq in preds:
                attr = cgq.attr
                rev = attr.startswith("~")
                tab = self._tablet(attr[1:] if rev else attr)
                if tab is None or tab.schema.value_type != TypeID.UID:
                    continue
                if rev and not tab.schema.reverse:
                    raise GQLError(
                        f"reverse edges are not defined for predicate "
                        f"{attr[1:]!r} (add @reverse to the schema)")
                # filtered recurse: ONE batched expansion per level
                # (device-capable) and one filter evaluation on the
                # level's union instead of once per parent (ref
                # recurse.go:29 — its per-level subgraph exec batches
                # over SrcUIDs the same way). Unfiltered recurse skips
                # the union pass: per-parent edge lists are needed for
                # the nested output regardless, and their concat IS the
                # union.
                union = None
                if cgq.filter is not None:
                    union = self._expand_level(tab, frontier, rev)
                    if len(union):
                        union = self._eval_filter(cgq.filter, union)
                    if not len(union):
                        level[attr] = {}
                        continue
                per_parent: dict[int, np.ndarray] = {}
                parts = []
                for u in frontier.tolist():
                    dst = (tab.get_reverse_uids(u, self.read_ts) if rev
                           else tab.get_dst_uids(u, self.read_ts))
                    if union is not None:
                        dst = _intersect(dst, union)
                    if len(dst):
                        per_parent[u] = dst
                        parts.append(dst)
                level[attr] = per_parent
                reached = union if union is not None else (
                    np.unique(np.concatenate(parts)) if parts else _EMPTY)
                if cgq.var and len(reached):
                    var_accum[cgq.var] = _union(
                        var_accum.get(cgq.var, _EMPTY), reached)
                if len(reached):
                    nxt = _union(nxt, reached)
            node.recurse_levels.append(level)
            if not allow_loop:
                nxt = _difference(nxt, visited)
                visited = _union(visited, nxt)
            else:
                visited = _union(visited, nxt)
            frontier = nxt
        for cgq in gq.children:
            if cgq.var and cgq.attr == "uid" and not cgq.is_count:
                # `a as uid` inside @recurse: every visited uid
                # (ref query3:TestRecurseVariableUid)
                var_accum[cgq.var] = _union(
                    var_accum.get(cgq.var, _EMPTY), visited)
        for name, uids in var_accum.items():
            self.uid_vars[name] = uids
        node.recurse_frontiers = None  # levels carry everything

    # ------------------------------------------------------------------
    # shortest path (ref query/shortest.go:451 Dijkstra / :287 k-paths)
    # ------------------------------------------------------------------

    def _run_shortest(self, node: ExecNode):
        """shortest(from, to, numpaths, depth, minweight, maxweight)
        with optional @facets(<key>) edge weights on the predicate
        children. Ref: query/shortest.go:451 route() (Dijkstra),
        :287 runKShortestPaths, gql/parser.go:2501 args.

        Every block runs under the span `shortest` (`depth`, `tier`;
        on the device tier `lanes`, `levels`, `program` too), counted
        in `shortest_tier_total{tier}`; the span's time accumulates
        in `shortest_ns_total`, so that less
        `device_call_ns_total{family="shortest"}` it is what a block
        costs off the chip."""
        gq = node.gq
        sa = gq.shortest
        if sa is None or sa.from_ is None or sa.to is None:
            raise GQLError("shortest requires from: and to:")
        src = self._fn_single_uid(sa.from_)
        dst = self._fn_single_uid(sa.to)
        pred_specs = self._shortest_preds(gq)
        maxdepth = sa.depth or 64
        weighted = any(w for _, _, _, w in pred_specs)
        simple = (sa.numpaths <= 1 and not weighted
                  and sa.minweight == float("-inf")
                  and sa.maxweight == float("inf"))
        t0 = _time.perf_counter_ns()
        try:
            with _span("shortest", depth=maxdepth) as sp:
                sp["tier"] = "host"
                path = self._one_path(pred_specs[0], src, dst, maxdepth,
                                      sp) \
                    if simple and len(pred_specs) == 1 else None
                if path is not None:
                    paths = [(path, float(len(path) - 1))] if path else []
                else:
                    paths = self._k_shortest(pred_specs, src, dst, maxdepth,
                                             max(1, sa.numpaths),
                                             sa.minweight, sa.maxweight)
                self._finish_shortest(node, paths, pred_specs)
                inc_counter("shortest_tier_total",
                            labels={"tier": sp["tier"]})
        finally:
            inc_counter("shortest_ns_total", _time.perf_counter_ns() - t0)

    def _one_path(self, spec: tuple, src: int, dst: int, depth: int,
                  sp: dict) -> Optional[list[int]]:
        """THE path of the simple block (one uid predicate, unweighted,
        one path: docs/deployment.md, "shortest"), [] where there is
        none, from the device tier (_device_shortest) or the host's
        (storage/tablet.least_path): the same path from either. None
        where the predicate is no local uid tablet (a federated proxy
        keeps the general search)."""
        _, tab, rev, _ = spec
        if tab.schema.value_type != TypeID.UID \
                or not hasattr(tab, "expand_in"):
            return None
        if src == dst:
            return [src]
        self._checkpoint("shortest")
        if self.db.prefer_device:
            path = self._device_shortest(tab, rev, src, dst, depth, sp)
            if path is not None:
                return path
        from dgraph_tpu.storage.tablet import least_path
        return least_path(tab, src, dst, depth, self.read_ts, rev)

    def _shortest_preds(self, gq) -> list[tuple]:
        """[(attr, tablet, reverse, weight_facet_key)] for the block's
        predicate children."""
        out = []
        for c in gq.children:
            if c.is_internal:
                continue
            pname = c.attr
            rev = pname.startswith("~")
            tab = self._tablet(pname[1:] if rev else pname)
            if tab is None:
                continue
            if rev and not tab.schema.reverse:
                raise GQLError(
                    f"reverse edges are not defined for predicate "
                    f"{pname[1:]!r} (add @reverse to the schema)")
            wkey = ""
            if c.facets is not None and c.facets.keys:
                wkey = c.facets.keys[0][0]
            out.append((pname, tab, rev, wkey))
        return out

    def _shortest_neighbors(self, pred_specs, u: int
                            ) -> list[tuple[int, float]]:
        """(neighbor, edge weight) pairs; facet weight when requested,
        else 1 per hop (ref shortest.go expandOut)."""
        out = []
        for pname, tab, rev, wkey in pred_specs:
            dsts = (tab.get_reverse_uids(u, self.read_ts) if rev
                    else tab.get_dst_uids(u, self.read_ts))
            for d in dsts.tolist():
                w = 1.0
                if wkey:
                    # facets live on the forward edge; an edge MISSING
                    # the weight facet is unusable in weighted mode
                    # (ref query3_test.go TestKShortestPathWeighted:
                    # only the fully-faceted route exists)
                    fsrc, fdst = (d, u) if rev else (u, d)
                    fv = tab.get_facets(fsrc, fdst, self.read_ts).get(wkey)
                    if fv is None:
                        continue
                    try:
                        w = float(fv.value)
                    except (TypeError, ValueError):
                        continue
                out.append((int(d), w))
        return out

    def _k_shortest(self, pred_specs, src: int, dst: int, maxdepth: int,
                    k: int, minw: float = float("-inf"),
                    maxw: float = float("inf")
                    ) -> list[tuple[list[int], float]]:
        """Yen's algorithm over hop-labeled Dijkstra: loopless shortest
        paths in nondecreasing weight until k of them fall inside the
        [minweight, maxweight] window (ref shortest.go:287
        runKShortestPaths — the weight bounds are search constraints,
        not a post-filter)."""
        import heapq

        nbr_memo: dict[int, list[tuple[int, float]]] = {}

        def neighbors(u: int):
            out = nbr_memo.get(u)
            if out is None:
                out = nbr_memo[u] = self._shortest_neighbors(
                    pred_specs, u)
            return out

        def dijkstra(banned_edges, banned_nodes, start, depth_budget):
            self._checkpoint("shortest")
            # labels are (node, hops): a cheap-but-deep route must not
            # shadow a shallower one that still has hop budget left
            dist = {(start, 0): 0.0}
            prev: dict[tuple[int, int], tuple[int, int]] = {}
            pq = [(0.0, 0, start)]
            best_dst = None
            while pq:
                if self.ctx is not None and (len(dist) & 0xFF) == 0:
                    self.ctx.check("shortest")
                d, hops, u = heapq.heappop(pq)
                if u == dst:
                    best_dst = (u, hops)
                    break
                if d > dist.get((u, hops), float("inf")) \
                        or hops >= depth_budget:
                    continue
                for v, w in neighbors(u):
                    if v in banned_nodes or (u, v) in banned_edges:
                        continue
                    nd = d + w
                    if nd < dist.get((v, hops + 1), float("inf")):
                        dist[(v, hops + 1)] = nd
                        prev[(v, hops + 1)] = (u, hops)
                        heapq.heappush(pq, (nd, hops + 1, v))
            if best_dst is None:
                return None
            path = [best_dst[0]]
            label = best_dst
            while label[0] != start or label[1] != 0:
                label = prev[label]
                path.append(label[0])
            path.reverse()
            return path, dist[best_dst]

        def in_window(w):
            return minw <= w <= maxw

        if src == dst:
            return [([src], 0.0)] if in_window(0.0) else []
        first = dijkstra(set(), set(), src, maxdepth)
        if first is None:
            return []
        found = [first]
        cand: list[tuple[float, list[int]]] = []
        seen = {tuple(first[0])}
        max_rounds = max(64, 8 * k)  # window search safety valve
        while sum(1 for _, w in found if in_window(w)) < k \
                and len(found) < max_rounds:
            base_path, base_w = found[-1]
            # prefix weights of the base path, one edge-lookup pass
            prefix_w = [0.0]
            for a, b in zip(base_path, base_path[1:]):
                ws = [w for v, w in neighbors(a) if v == b]
                prefix_w.append(prefix_w[-1] + (min(ws) if ws else 1.0))
            for i in range(len(base_path) - 1):
                spur = base_path[i]
                root = base_path[: i + 1]
                banned_edges = {(p[i], p[i + 1]) for p, _ in found
                                if len(p) > i + 1 and p[: i + 1] == root}
                banned_nodes = set(root[:-1])
                rest = dijkstra(banned_edges, banned_nodes, spur,
                                maxdepth - i)
                if rest is None:
                    continue
                total = root[:-1] + rest[0]
                key = tuple(total)
                if key not in seen:
                    seen.add(key)
                    heapq.heappush(cand, (prefix_w[i] + rest[1], total))
            if not cand:
                break
            w, p = heapq.heappop(cand)
            if w > maxw:
                break  # nondecreasing weights: nothing ahead can fit
            found.append((p, w))
        return [(p, w) for p, w in found if in_window(w)][:k]

    def _finish_shortest(self, node: ExecNode, paths, pred_specs=None):
        node.path_nodes = [p for p, _ in paths]
        node.path_weights = [w for _, w in paths]
        node.path_specs = pred_specs or []
        gq = node.gq
        if gq.var:
            # the uid var holds the FIRST (best) path, ref shortest.go
            if paths:
                self.uid_vars[gq.var] = _np_sorted(paths[0][0])
                # consumers of a PATH var emit in traversal order, not
                # uid order (ref query3_test.go TestShortestPathRev)
                self._path_var_order[gq.var] = list(paths[0][0])
            else:
                self.uid_vars[gq.var] = _EMPTY

    def _device_shortest(self, tab: Tablet, rev: bool, src: int, dst: int,
                         depth: int, sp: dict) -> Optional[list[int]]:
        """_one_path's device tier: the pair rides ONE call of
        bitgraph.bfs_paths with the other `shortest` blocks in flight
        over the tile (a Rendezvous of family `shortest`, eight lanes
        a call, a full call queued behind the one in flight), and
        fetches its own row of the call's result: the path's slots,
        chosen on the device. -> the path's uids, [] where there is
        none within `depth`; None where the host tier is to answer:
        the gate says so, or the device cannot speak for the search
        (uids over 32 bits, a dirty tablet or one under
        device_min_edges, an engine whose mesh splits the predicate:
        bfs_paths has no sharded form).

        The family's `_device_worth` site, as _recurse_device is the
        k-hop family's: the host's cost from the depth and the
        tablet's degree moments (planner.shortest_costs), the
        device's from the expected levels and the adjacency's layout
        (bitgraph.level_seconds), both before the search runs. The
        distances TO the target are pulled along out-neighbours, so
        the tile is the TRANSPOSED one of the direction the path
        follows, with its hub rows and its slots' uids."""
        from dgraph_tpu.engine.device_cache import (
            _MAX_U32, device_bitadjacency, uid_mesh,
        )
        from dgraph_tpu.ops import bitgraph
        from dgraph_tpu.query.planner import shortest_costs
        if max(src, dst) > _MAX_U32 or uid_mesh(self.db) is not None:
            return None
        moments = tab.degree_moments(rev)
        host, levels = shortest_costs(depth, *moments)
        worth = functools.partial(self._device_beats, host)
        if not worth(levels * 4 * moments[1] / bitgraph.DENSE_BYTES_PER_S):
            return None
        badj = device_bitadjacency(self.db, tab, self.read_ts,
                                   transpose=not rev, dense=True, walk=True)
        if badj is None or badj.n_slots == 0 \
                or not worth(levels * bitgraph.level_seconds(badj)):
            return None
        with device_call("query_device_shortest_total", sink=self.lat,
                         program="bfs_paths") as dc:
            slots, hit = bitgraph._uid_slots(
                badj, np.asarray([src, dst], np.uint32))
            if not hit.all():
                return []       # a uid no edge of the predicate touches
            meet = Rendezvous.at(badj, bitgraph.LANES, family="shortest")
            ride = dc.wait_for(
                lambda: meet.ride(
                    (int(slots[0]), int(slots[1]), depth),
                    functools.partial(_launch_paths, badj),
                    _land_paths, self.ctx),
                out_bytes=4 * (2 + bitgraph.path_width(depth, badj.n_slots)))
            row, (ran, streamed, full, columns) = ride.result
            batch = {"lanes": ride.lanes, "levels": ran,
                     "batch_wait_us": ride.waited_ns // 1000,
                     "program": "bfs_paths", "hub_tiles_streamed": streamed,
                     "hub_tiles": full, "column_levels": columns}
            dc.note(**batch)
            path = bitgraph.path_uids(badj, row)
        if path is not None:
            sp.update(tier="device", hops=len(path) - 1, **batch)
        return path

    def _fn_single_uid(self, fn: Function) -> int:
        if fn.uids:
            return fn.uids[0]
        for vc in fn.needs_var:
            arr = self.uid_vars.get(vc.name, _EMPTY)
            if len(arr):
                return int(arr[0])
        raise GQLError("shortest from/to resolved to no uid")

    # ------------------------------------------------------------------
    # output (ref query/outputnode.go:653 preTraverse)
    # ------------------------------------------------------------------

    def _cascade_rebind_vars(self, node: ExecNode):
        """Prune every var bound inside a @cascade block the way the
        reference's applyCascade does BEFORE var population (ref
        query.go applyCascade; query3:TestUseVarsCascade): two passes —
        bottom-up per-uid subtree satisfaction (_cascade_keep), then
        top-down parent reachability, so a uid bound through a parent
        the cascade dropped (e.g. for a missing sibling scalar) is
        unbound too."""
        memo: dict[int, np.ndarray] = {}
        self._cascade_edge_cache: dict[tuple, np.ndarray] = {}
        alive = self._cascade_keep(node, memo)
        if node.gq.var:
            self.uid_vars[node.gq.var] = alive
        self._cascade_descend(node, alive, memo)
        self._cascade_edge_cache = {}

    def _cascade_edges(self, c: ExecNode, u: int) -> np.ndarray:
        """Per-(child, parent) edge list, cached across the keep and
        descend passes so each tablet edge list is read once."""
        key = (id(c), u)
        got = self._cascade_edge_cache.get(key)
        if got is None:
            get = c.tablet.get_reverse_uids if c.reverse \
                else c.tablet.get_dst_uids
            got = get(u, self.read_ts)
            self._cascade_edge_cache[key] = got
        return got

    def _cascade_table(self, c: ExecNode):
        """Flat (parent_keys sorted, child_uids) columnar edge table in
        the child's direction for a CLEAN tablet — the same
        searchsorted join surface _join_codes consumes — or None
        (dirty tablets keep the exact per-uid MVCC loop). Reverse
        children pay one lexsort to flip the forward table; cached for
        the cascade pass like the per-parent edge lists."""
        key = ("table", id(c))
        got = self._cascade_edge_cache.get(key, False)
        if got is not False:
            return got
        et = c.tablet.edge_table(self.read_ts) \
            if self._columnar_on() and hasattr(c.tablet, "edge_table") \
            else None
        out = None
        if et is not None:
            srcs, dsts = et
            if c.reverse:
                order = np.argsort(dsts, kind="stable")
                out = (dsts[order], srcs[order])
            else:
                out = (srcs, dsts)
        self._cascade_edge_cache[key] = out
        return out

    def _cascade_descend(self, node: ExecNode, alive: np.ndarray,
                         memo: dict):
        for c in node.children:
            if c.gq.attr == "uid" and c.gq.var and not c.gq.is_count:
                # `x as uid` binds the SURVIVING parents
                self.uid_vars[c.gq.var] = alive
                continue
            if c.tablet is None or c.gq.is_count:
                continue
            if c.tablet.schema.value_type == TypeID.UID or c.reverse:
                table = self._cascade_table(c)
                if table is not None and len(alive):
                    # columnar: gather every edge of the surviving
                    # parents with ONE searchsorted join (_join_codes)
                    # instead of a per-parent edge-fetch loop
                    got = _join_codes(table[0], table[1], alive)
                    reach = np.unique(got[1]) if got is not None \
                        else _EMPTY
                else:
                    parts = [self._cascade_edges(c, int(p))
                             for p in alive.tolist()]
                    parts = [p for p in parts if len(p)]
                    reach = np.unique(np.concatenate(parts)) if parts \
                        else _EMPTY
                alive_c = _intersect(
                    _intersect(reach, c.dest),
                    self._cascade_keep(c, memo))
                if c.gq.var:
                    self.uid_vars[c.gq.var] = alive_c
                self._cascade_descend(c, alive_c, memo)
            elif c.gq.var:
                # scalar value var: restrict its domain to surviving
                # parents
                vm = self.value_vars.get(c.gq.var)
                if isinstance(vm, dict):
                    keep = set(alive.tolist())
                    self.value_vars[c.gq.var] = {
                        u: v for u, v in vm.items() if u in keep}
                elif isinstance(vm, ColVar):
                    self.value_vars[c.gq.var] = vm.take(alive)

    def _cascade_keep(self, node: ExecNode, memo: dict) -> np.ndarray:
        """dest uids satisfying node's OWN subtree constraints,
        bottom-up (an edge child's targets must themselves satisfy
        theirs). Parent reachability is _cascade_descend's job."""
        key = id(node)
        if key in memo:
            return memo[key]
        keep = node.dest
        for c in node.children:
            if c.tablet is None or c.gq.is_count or not len(keep):
                continue
            if c.tablet.schema.value_type == TypeID.UID or c.reverse:
                sub = self._cascade_keep(c, memo) if c.children \
                    else c.dest
                table = self._cascade_table(c)
                if table is not None:
                    # columnar: one searchsorted join gathers every
                    # parent's edges, one membership test against
                    # `sub` keeps parents with >= 1 surviving edge —
                    # no per-(child, parent) Python loop
                    got = _join_codes(table[0], table[1], keep)
                    ok = np.zeros(len(keep), bool)
                    if got is not None and len(sub):
                        rep, gathered = got
                        hit = _member_of(gathered, sub)
                        ok[rep[hit]] = True
                    keep = keep[ok]
                else:
                    keep = np.asarray(
                        [u for u in keep.tolist()
                         if len(_intersect(
                             self._cascade_edges(c, int(u)), sub))],
                        dtype=np.uint64)
            else:
                keep = np.asarray(
                    [u for u in keep.tolist()
                     if self._cascade_scalar_present(c, int(u))],
                    dtype=np.uint64)
        memo[key] = keep
        return keep

    def _cascade_scalar_present(self, c: ExecNode, u: int) -> bool:
        """Same presence predicate the emission-time cascade applies:
        col_vals is authoritative when built; otherwise the posting
        list filtered through the child's language selectors (a var
        block skips scalar materialization, so fall through to the
        tablet)."""
        if c.col_vals is not None:
            return c.col_vals.get(u) is not None
        ps = c.values.get(u)
        if not ps:
            ps = c.tablet.get_postings(u, self.read_ts)
        if ps and c.gq.facets_filter is not None:
            # same value-facet filter the emission applies (ref
            # facets:TestFacetsFilterAtValueBasic)
            ps = [p for p in ps
                  if self._eval_facet_tree(c.gq.facets_filter,
                                           p.facets)]
        if not ps:
            return False
        if c.gq.langs == ["*"]:
            return True
        return self._select_posting(ps, c.gq.langs or []) is not None

    def _emit_block(self, node: ExecNode) -> list:
        gq = node.gq
        if gq.recurse is not None:
            self._recurse_colvals = self._recurse_scalar_cache(node)
            try:
                return [r for r in
                        (self._emit_recurse_node(node, int(u), 0)
                         for u in node.dest.tolist()) if r]
            finally:
                self._recurse_colvals = {}
        if gq.is_groupby:
            # root-level @groupby groups the block's matched uids (ref
            # query0_test.go TestGroupByRoot:
            # {"me":[{"@groupby":[...]}]}); ZERO groups omit the
            # whole block key (TestGroupByRootEmpty -> {})
            fake = ExecNode(gq)
            grp = self._emit_groupby(fake, node.dest)
            return [grp] if grp.get("@groupby") else []
        if not node.children:
            # empty selection: rows emit nothing (ref query0:
            # TestMultiEmptyBlocks -> "you": [])
            return []
        for ch in node.children:
            self._ensure_child_values(ch)
        fast = self._emit_block_flat(node)
        if fast is not None:
            return fast
        out = []
        # count(uid) at block level: one summed object
        # (ref outputnode.go uid count emission)
        n_counts = 0
        for ch in node.children:
            if ch.gq.attr == "uid" and ch.gq.is_count:
                out.append({ch.gq.alias or "count":
                            node.root_count if node.root_count >= 0
                            else len(node.dest)})
                n_counts += 1
        if n_counts and n_counts == len(node.children):
            # count-only block: the per-uid walk below would emit (and
            # drop) an empty object per row — 0.5s of the 21M q009
            return out
        order = node.emit_order if node.emit_order is not None \
            else node.dest.tolist()
        for u in order:
            # @ignorereflex: track the result path so children never
            # re-emit an ancestor (ref query.go:164 removeCycles)
            path = frozenset({int(u)}) if gq.ignore_reflex else None
            obj = self._emit_uid(node, int(u), path,
                                 normalize=gq.normalize)
            if obj:  # empty objects are dropped (ref outputnode.go)
                out.append(obj)
        # row-less blocks (q() { min(val(a)) }) emit aggregations as
        # standalone objects; blocks WITH rows attach them per row in
        # _emit_uid (ref preTraverse)
        if not len(node.dest):
            for ch in node.children:
                if ch.gq.agg_func and 0 in ch.values:
                    agg = ch.values[0][0]
                    if agg.value is not None:
                        name = ch.gq.alias or ch.gq.attr
                        out.append({name: to_json_value(agg.value)})
                elif ch.gq.math is not None and 0 in ch.values:
                    # math over aggregated (global) vars in a row-less
                    # block (ref query1:TestAggregateRoot4 `Sum:
                    # math(minVal + maxVal)`); same naming convention
                    # as the per-row path: `v as math(...)` emits
                    # under "val(v)"
                    agg = ch.values[0][0]
                    if agg.value is not None:
                        name = ch.gq.alias or (
                            f"val({ch.gq.var})" if ch.gq.var
                            else "math")
                        out.append({name: to_json_value(agg.value)})
        if gq.normalize:
            out = [row for o in out if o
                   for row in self._normalize(o)]
            out = [o for o in out if o]
        return out

    def _emit_block_flat(self, node: ExecNode) -> Optional[list]:
        """Dict-output twin of _emit_block_flat_json: a uid block whose
        children are all `uid` fields or columnar scalars (col_vals
        built) emits via one tight gather loop — the general _emit_uid
        walk re-decides langs/facets/cascade per row and dominated
        flat-block profiles (q003). None keeps the exact emitter."""
        gq = node.gq
        if gq.normalize or gq.cascade or gq.ignore_reflex:
            return None
        specs = []
        for ch in node.children:
            cgq = ch.gq
            if cgq.attr == "uid" and not cgq.is_count:
                specs.append((cgq.alias or "uid", None))
            elif ch.col_vals is not None and not cgq.is_count:
                specs.append((cgq.alias or cgq.attr, ch.col_vals))
            else:
                return None
        order = node.emit_order if node.emit_order is not None \
            else node.dest.tolist()
        out = []
        for u in order:
            obj = {}
            for name, cv in specs:
                if cv is None:
                    obj[name] = hex(u)
                else:
                    v = cv.get(u)
                    if v is not None:
                        obj[name] = v
            if obj:  # empty objects drop (ref outputnode.go)
                out.append(obj)
        return out

    def _emit_uid(self, node: ExecNode, uid: int,
                  path: Optional[frozenset] = None,
                  cascade: bool = False,
                  normalize: bool = False) -> Optional[dict]:
        obj: dict[str, Any] = {}
        gq = node.gq
        # @cascade and @normalize apply to the WHOLE subtree under the
        # block that declares them (ref query.go applyCascade;
        # @normalize keeps ONLY aliased attributes —
        # query2_test.go TestNormalizeDirective drops bare `gender`)
        cascade = cascade or gq.cascade
        normalize = normalize or gq.normalize
        have: set[str] = set()  # names satisfied but normalize-hidden
        children = node.children
        if not children:
            obj["uid"] = hex(uid)
            return obj
        for ch in children:
            cgq = ch.gq
            name = cgq.alias or cgq.attr
            if normalize and not cgq.alias and ch.tablet is not None \
                    and ch.tablet.schema.value_type != TypeID.UID \
                    and not (cgq.is_count or ch.reverse):
                # @normalize: bare scalars don't emit — but @cascade's
                # presence check still counts a value that EXISTS
                if (ch.col_vals or {}).get(uid) is not None \
                        or ch.values.get(uid):
                    have.add(name)
                continue
            if normalize and not cgq.alias and cgq.attr == "uid" \
                    and not cgq.is_count:
                continue
            if cgq.langs and not cgq.alias:
                name = f"{cgq.attr}@{':'.join(cgq.langs)}"
            if cgq.attr == "uid":
                if cgq.is_count:
                    continue  # count(uid) handled at parent level
                obj[cgq.alias or "uid"] = hex(uid)
                continue
            if normalize and not cgq.alias \
                    and (cgq.agg_func or cgq.attr == "math"
                         or cgq.attr.startswith("val(")
                         or cgq.is_count):
                continue
            if cgq.agg_func:
                # aggregations attach INSIDE each parent row (ref
                # outputnode.go preTraverse: the agg subgraph hangs
                # under its parent node — TestLevelBasedFacetVarAggSum
                # shape); per-parent (level-based) aggregates emit the
                # parent's own value under the VAR name; row-less
                # blocks emit them standalone in _emit_block instead
                vs = ch.values.get(uid)
                if vs is not None and cgq.var:
                    name = cgq.alias or cgq.var
                if vs is None:
                    vs = ch.values.get(0)
                if vs is not None and vs[0].value is not None:
                    obj[name] = to_json_value(vs[0].value)
                continue
            if cgq.attr == "math" or cgq.attr.startswith("val("):
                if cgq.attr == "math" and cgq.var and not cgq.alias:
                    # `sum as math(...)` emits under "val(sum)" (ref
                    # TestQueryVarValAggOrderDesc expected shape)
                    name = f"val({cgq.var})"
                vs = ch.values.get(uid)
                if vs:
                    obj[name] = to_json_value(vs[0].value)
                continue
            if cgq.checkpwd_pwd is not None:
                vs = ch.values.get(uid)
                if vs is not None:
                    obj[cgq.alias or f"checkpwd({cgq.attr})"] = \
                        to_json_value(vs[0].value)
                continue
            if ch.tablet is None:
                continue
            if cgq.is_count:
                cname = cgq.alias or f"count({cgq.attr})"
                obj[cname] = ch.counts.get(uid, 0)
                continue
            tab = ch.tablet
            if tab.schema.value_type == TypeID.UID and not ch.reverse \
                    or (ch.reverse and tab.schema.reverse):
                if cgq.facets_filter is not None:
                    dsts = self._edge_dsts_facet_filtered(
                        tab, uid, ch.reverse, cgq.facets_filter)
                else:
                    dsts = (tab.get_reverse_uids(uid, self.read_ts)
                            if ch.reverse
                            else tab.get_dst_uids(uid, self.read_ts))
                dsts = _intersect(dsts, ch.dest) if len(ch.dest) else \
                    (dsts if not ch.gq.filter else _EMPTY)
                if path is not None and len(dsts):
                    dsts = _difference(dsts, _np_sorted(path))
                if cgq.is_groupby:
                    # the reference emits child groupby as a one-
                    # element array (query0_test.go TestGroupBy shape);
                    # a repeated attr merges into one key in child
                    # order (TestGroupBy_RepeatAttr); ZERO groups
                    # emit nothing so a member-less parent row drops
                    # (TestGroupByAgeMultiParents skips uids 99999/8)
                    grp = self._emit_groupby(ch, dsts)
                    if grp.get("@groupby"):
                        _merge_list_key(obj, name, [grp])
                    continue
                facet_orders = [o for o in cgq.order
                                if o.attr.startswith("facet:")]
                if facet_orders:
                    dsts = self._order_paginate_facets(
                        cgq, tab, uid, ch.reverse, dsts, facet_orders)
                else:
                    dsts = self._order_paginate(cgq, dsts)
                counts = [c for c in cgq.children
                          if c.attr == "uid" and c.is_count]
                if counts and all(c.attr == "uid" and c.is_count
                                  for c in cgq.children):
                    obj[name] = [{counts[0].alias or "count": len(dsts)}]
                    continue
                if cgq.facets is not None \
                        and hasattr(tab, "prefetch_facets"):
                    # federated: one facets RPC per parent, over the
                    # PAGINATED edge list only (the level-wide
                    # prefetch would ship every edge's facets on
                    # first: N queries)
                    tab.prefetch_facets(
                        [((int(d), uid) if ch.reverse
                          else (uid, int(d))) for d in dsts.tolist()])
                items = []
                for d in dsts.tolist():
                    sub = self._emit_uid(
                        ch, int(d),
                        path | {int(d)} if path is not None else None,
                        cascade or cgq.cascade,
                        normalize or cgq.normalize)
                    if sub is None:
                        continue
                    if cgq.facets is not None:
                        fsrc, fdst = (int(d), uid) if ch.reverse \
                            else (uid, int(d))
                        fc = tab.get_facets(fsrc, fdst, self.read_ts)
                        self._attach_facets(sub, cgq.facets, fc, name)
                    if sub:
                        items.append(sub)
                if counts and len(dsts):
                    # count(uid) alongside siblings: the count rides
                    # as an extra row object even when every sibling
                    # row came up empty — but an empty EDGE LIST emits
                    # no key at all (ref query1_test.go
                    # TestCountAtRoot3: Daryl has count(friend):0 and
                    # NO friend key)
                    items.append({counts[0].alias or "count":
                                  len(dsts)})
                if items:
                    # a non-list uid predicate emits its single target
                    # as an OBJECT (ref query0_test.go
                    # TestGetNonListUidPredicate); reverse edges and
                    # count-carrying lists stay list-shaped
                    if not tab.schema.list_ and not ch.reverse \
                            and not counts and name not in obj:
                        obj[name] = items[0]
                    else:
                        _merge_list_key(obj, name, items)
                elif cascade:
                    # only an INHERITED cascade scope drops the
                    # parent; @cascade declared ON this child governs
                    # the child's own subtree — the parent just emits
                    # without the field (ref query4:TestCascadeSubQuery1)
                    return None
            else:
                if ch.col_vals is not None:
                    v = ch.col_vals.get(uid)
                    if v is not None:
                        obj[name] = v
                        continue
                    if cascade:
                        return None
                    continue
                ps = ch.values.get(uid)
                if ps and cgq.facets_filter is not None:
                    # @facets(eq(k, v)) on a VALUE predicate keeps
                    # only postings whose facets match (ref facets:
                    # TestFacetsFilterAtValueBasic — rows whose value
                    # fails the filter emit nothing)
                    ps = [p for p in ps
                          if self._eval_facet_tree(
                              cgq.facets_filter, p.facets)]
                if ps and cgq.langs == ["*"]:
                    # name@* : every language as its own key, the
                    # untagged value under the bare attr (ref
                    # query0_test.go TestQueryAllLanguages)
                    emitted = False
                    for p in ps:
                        key = f"{cgq.attr}@{p.lang}" if p.lang \
                            else cgq.attr
                        # canonical per-language keys; an alias can't
                        # name several keys, so it is ignored here
                        obj[key] = to_json_value(
                            self._typed(ch.tablet, p))
                        emitted = True
                    if emitted:
                        continue
                elif ps:
                    v = self._emit_value(ch, ps)
                    if v is not None:
                        obj[name] = v
                        if cgq.facets is not None:
                            self._attach_value_facets(obj, ch, ps, name)
                        continue
                if cascade:
                    return None
        if cascade:
            want = [c for c in children
                    if c.tablet is not None and not c.gq.is_count]
            for c in want:
                nm = c.gq.alias or c.gq.attr
                if nm not in obj and nm not in have:
                    return None
        return obj

    def _emit_value(self, ch: ExecNode, ps) -> Any:
        cgq = ch.gq
        tab = ch.tablet
        if tab.schema.value_type == TypeID.PASSWORD:
            # password hashes are never fetchable — only checkpwd()
            # reads them (ref query3:TestQueryPassword)
            return None
        if tab.schema.list_:
            vals = [to_json_value(self._typed(tab, p)) for p in ps
                    if not p.lang]
            return vals or None
        if cgq.langs:
            sel = self._select_posting(ps, cgq.langs)
            return to_json_value(self._typed(tab, sel)) if sel else None
        sel = self._select_posting(ps, [])
        return to_json_value(self._typed(tab, sel)) if sel else None

    def _order_paginate_facets(self, gq: GraphQuery, tab: Tablet,
                               parent: int, reverse: bool,
                               dsts: np.ndarray, orders) -> np.ndarray:
        """@facets(orderasc: k): sort a parent's edge list by facet
        value, missing-facet edges last (ref query.go sortWithFacet)."""
        def keys_for(d):
            row = []
            for o in orders:
                key = o.attr[len("facet:"):]
                fsrc, fdst = (int(d), parent) if reverse \
                    else (parent, int(d))
                fv = tab.get_facets(fsrc, fdst, self.read_ts).get(key)
                if fv is None:
                    row.append((1, 0))
                else:
                    try:
                        k = sort_key(fv)
                    except ValueError:
                        k = 0
                    row.append((0, -k if o.desc else k))
            row.append((0, int(d)))
            return tuple(row)

        ordered = np.asarray(sorted(dsts.tolist(), key=keys_for),
                             dtype=np.uint64)
        # pagination still applies after the facet sort
        stripped = GraphQuery(attr=gq.attr, first=gq.first,
                              offset=gq.offset, after=gq.after)
        return self._order_paginate(stripped, ordered)

    def _attach_value_facets(self, obj: dict, ch: ExecNode, ps,
                             name: str):
        """name|key facets of value postings; list predicates emit a
        position-indexed map (ref outputnode.go facetsNode handling)."""
        cgq = ch.gq
        fp = cgq.facets
        tab = ch.tablet
        if tab.schema.list_:
            plist = [p for p in ps if not p.lang]
            by_key: dict[str, dict[str, Any]] = {}
            for i, p in enumerate(plist):
                sel = p.facets if fp.all_keys else {
                    k: p.facets[k] for k, _ in fp.keys if k in p.facets}
                for k, v in sel.items():
                    by_key.setdefault(k, {})[str(i)] = to_json_value(v)
            alias = {} if fp.all_keys else \
                {k: a for k, a in fp.keys if a}
            for k, m in by_key.items():
                obj[alias.get(k) or f"{name}|{k}"] = m
            return
        sel = self._select_posting(ps, cgq.langs)
        if sel is not None and sel.facets:
            self._attach_facets(obj, fp, sel.facets, name)

    def _attach_facets(self, item: dict, fp, facets: dict, edge: str):
        if not facets:
            return
        sel = facets if fp.all_keys else {
            k: facets[k] for k, _ in fp.keys if k in facets}
        alias = {} if fp.all_keys else \
            {k: a for k, a in fp.keys if a}
        for k, v in sel.items():
            # an ALIASED facet emits under the bare alias; unaliased
            # ones keep the edge|key form (ref facets:TestFacetsAlias:
            # `tagalias: tag` -> "tagalias", bare `family` ->
            # "friend|family")
            key = alias.get(k) or f"{edge}|{k}"
            item[key] = to_json_value(v)

    def _groupby_groups(self, gq: GraphQuery, dsts: np.ndarray
                        ) -> dict[tuple, list[int]]:
        """Group member uids by the tuple of their @groupby attr values
        (ref query/groupby.go:371 processGroupBy). Multi-valued attrs
        fan a member into every combination; members missing any
        grouped attr are dropped (the reference's dedupMap only sees
        uids that produced a value for each predicate)."""
        from itertools import product

        fast = self._groupby_groups_vec(gq.groupby, dsts)
        if fast is not None:
            return fast
        groups: dict[tuple, list[int]] = {}
        for d in dsts.tolist():
            per_attr: list[list] = []
            for ga in gq.groupby:
                tab = self._tablet(ga.attr)
                vals: list = []
                if tab is not None:
                    if tab.schema.value_type == TypeID.UID:
                        vals = [hex(t) for t in tab.get_dst_uids(
                            d, self.read_ts).tolist()]
                    else:
                        # list-valued scalars fan into every value's
                        # group; ga.lang selects that language's
                        # postings, default the untagged ones
                        ps = tab.get_postings(d, self.read_ts)
                        want = ga.lang or ""
                        seen = set()
                        for p in ps:
                            if p.lang != want:
                                continue
                            v = to_json_value(self._typed(tab, p))
                            k = v if isinstance(v, (str, int, float,
                                                    bool)) else str(v)
                            if k not in seen:
                                seen.add(k)
                                vals.append(v)
                if not vals:
                    per_attr = []
                    break
                per_attr.append(vals)
            if not per_attr:
                continue
            for combo in product(*per_attr):
                groups.setdefault(tuple(combo), []).append(int(d))
        return groups

    def _groupby_attr_codes(self, ga):
        """One @groupby attr as a vectorized key column:
        (uids sorted u64, codes int64 aligned, decode) where decode
        maps a code back to the output key value. uid predicates fan
        out via their flat edge table (need_pairs marks them); scalar
        predicates contribute one (uid, code) per valued member.
        Returns None -> caller keeps the exact per-uid path."""
        tab = self._tablet(ga.attr)
        if tab is None or not self._columnar_on():
            return None
        if tab.schema.value_type == TypeID.UID:
            if ga.lang or not hasattr(tab, "edge_table"):
                return None
            et = tab.edge_table(self.read_ts)
            if et is None:
                return None
            srcs, dsts = et
            # dst uids ARE the codes — kept uint64 (an int64 cast
            # would render uids >= 2^63 as negative hex)
            return srcs, dsts, lambda c: hex(int(c))
        col = self._colview(tab, lang=ga.lang or None)
        if col is None:
            return None
        srcs, tid, data, enc = col
        if data is not None:
            if tid == TypeID.BOOL:
                return srcs, data.astype(np.int64), \
                    lambda c: bool(c)
            if tid == TypeID.FLOAT:
                if np.isnan(data).any():
                    return None  # nan keys keep dict semantics
                # float keys: code through the unique table to stay
                # integral for the lexsort/boundary pass
                uk = np.unique(data)
                return srcs, np.searchsorted(uk, data), \
                    lambda c, _uk=uk: float(_uk[int(c)])
            return srcs, data.astype(np.int64), lambda c: int(c)
        got = col.enc_codes()
        if got is None:
            return None
        codes, table = got

        def dec(c, _t=table):
            return _t[int(c)].decode("utf-8")

        # count-fast extras: bulk decode (no per-element dispatch)
        # and, when byte order == output order, permission to skip
        # the per-group python sort altogether
        dec.bulk = lambda cs, _t=table: \
            [_t[c].decode("utf-8") for c in cs]
        dec.byte_ordered = col.enc_sort_safe() \
            if hasattr(col, "enc_sort_safe") else False
        return srcs, codes, dec

    def _groupby_groups_vec(self, gattrs, dsts: np.ndarray
                            ) -> Optional[dict[tuple, list[int]]]:
        """Vectorized grouping for ANY @groupby attr list (ref
        query/groupby.go:371 processGroupBy): each attr's keys come
        from columnar views (cached integer codes for strings, flat
        edge tables for uid fan-out), members join against them with
        searchsorted ranges, and the combined key tuples group via one
        lexsort + boundary scan — no per-uid posting walks. Returns
        None (exact path) when any attr lacks a clean columnar view."""
        cols = []
        for ga in gattrs:
            got = self._groupby_attr_codes(ga)
            if got is None:
                return None
            cols.append(got)
        rows = np.ascontiguousarray(dsts, dtype=np.uint64)
        code_cols: list[np.ndarray] = []
        for (u_sorted, codes, _dec) in cols:
            got = _join_codes(u_sorted, codes, rows)
            if got is None:
                return {}
            rep, gathered = got
            code_cols = [c[rep] for c in code_cols]
            code_cols.append(gathered)
            rows = rows[rep]
        if not len(rows):
            return {}
        order = np.lexsort(tuple(reversed(code_cols)))
        sorted_cols = [c[order] for c in code_cols]
        rows_s = rows[order]
        change = np.zeros(len(rows_s), bool)
        change[0] = True
        for c in sorted_cols:
            change[1:] |= c[1:] != c[:-1]
        bidx = np.nonzero(change)[0]
        bounds = np.append(bidx, len(rows_s)).tolist()
        inc_counter("query_groupby_fast_total")
        groups: dict[tuple, list[int]] = {}
        members = rows_s.tolist()
        for g in range(len(bidx)):
            s, e = bounds[g], bounds[g + 1]
            key = tuple(cols[k][2](sorted_cols[k][s])
                        for k in range(len(cols)))
            groups[key] = members[s:e]
        return groups

    def _groupby_entry(self, gq: GraphQuery, key: tuple,
                       members: list[int]) -> dict:
        """One output group: keys + count(uid) + aggregations over
        value vars (ref groupby.go aggregateGroup)."""
        ent: dict[str, Any] = {}
        for ga, kv in zip(gq.groupby, key):
            ent[ga.alias or ga.attr] = kv
        for cgq in gq.children:
            if cgq.attr == "uid" and cgq.is_count:
                ent[cgq.alias or "count"] = len(members)
            elif cgq.agg_func and cgq.needs_var:
                vmap = self.value_vars.get(cgq.needs_var[0].name, {})
                agg = _agg_members(cgq.agg_func, vmap, members)
                if agg is not None:
                    name = cgq.alias or \
                        f"{cgq.agg_func}(val({cgq.needs_var[0].name}))"
                    ent[name] = to_json_value(agg)
            elif cgq.agg_func and cgq.agg_pred:
                # max(name): aggregate a PREDICATE over the group's
                # members (ref query0_test.go TestGroupByAgg)
                agg = self._agg_pred_members(cgq, members)
                if agg is not None:
                    name = cgq.alias or \
                        f"{cgq.agg_func}({cgq.agg_pred})"
                    ent[name] = to_json_value(agg)
        return ent

    def _agg_pred_members(self, cgq, members) -> Optional[Val]:
        tab = self._tablet(cgq.agg_pred)
        if tab is None:
            return None
        if not cgq.langs:
            colview = self._colview(tab)
            if colview is not None and colview.data is not None \
                    and colview.tid in (TypeID.INT, TypeID.FLOAT):
                # max(name)-style predicate aggregation over a group:
                # one gather in MEMBER order (float-sum rounding equals
                # the posting walk's left fold) instead of a
                # get_postings round per member. Untagged selection ==
                # the column's own selection; tagged postings are never
                # picked by an empty lang list, so extras don't matter
                marr = np.asarray(members, np.uint64)
                pos, hit = _col_positions(colview.srcs, marr)
                arr = colview.data[pos[hit]]
                if not len(arr):
                    return None
                tid = colview.tid
                fn = cgq.agg_func
                if fn == "min":
                    return Val(tid, arr[int(np.argmin(arr))].item())
                if fn == "max":
                    return Val(tid, arr[int(np.argmax(arr))].item())
                if fn in ("sum", "avg"):
                    s = sum(arr.tolist())
                    if fn == "avg":
                        return Val(TypeID.FLOAT, s / len(arr))
                    return Val(TypeID.INT if isinstance(s, int)
                               else TypeID.FLOAT, s)
                return None
        vals = []
        for u in members:
            ps = tab.get_postings(int(u), self.read_ts)
            sel = self._select_posting(ps, cgq.langs or [])
            if sel is not None:
                vals.append(self._typed(tab, sel))
        return _aggregate(cgq.agg_func, vals)

    def _emit_groupby(self, ch: ExecNode, dsts: np.ndarray) -> dict:
        """@groupby(attrs...) { count(uid) aggs... }
        (ref query/groupby.go:371)."""
        fast = self._emit_groupby_count_fast(ch.gq, dsts)
        if fast is not None:
            return fast
        groups = self._groupby_groups(ch.gq, dsts)
        return {"@groupby": [
            self._groupby_entry(ch.gq, key, members)
            for key, members in sorted(groups.items(),
                                       key=lambda kv: str(kv[0]))]}

    def _emit_groupby_count_fast(self, gq: GraphQuery,
                                 dsts: np.ndarray) -> Optional[dict]:
        """Single-attr @groupby whose only child is count(uid): group
        counts come from one np.unique over the gathered key codes —
        no member lists, no per-group entry builder. This is the root
        groupby shape (q052/ref query0:TestGroupByRoot) where the
        general path's per-group Python dominated at 21M."""
        if len(gq.groupby) != 1 or len(gq.children) != 1:
            return None
        cgq = gq.children[0]
        if cgq.attr != "uid" or not cgq.is_count or cgq.var:
            return None
        got = self._groupby_attr_codes(gq.groupby[0])
        if got is None:
            return None
        u_sorted, codes, dec = got
        rows = np.ascontiguousarray(dsts, dtype=np.uint64)
        joined = _join_codes(u_sorted, codes, rows)
        if joined is None:
            return {"@groupby": []}
        uniq, counts = np.unique(joined[1], return_counts=True)
        inc_counter("query_groupby_fast_total")
        ga = gq.groupby[0]
        keyname = ga.alias or ga.attr
        cname = cgq.alias or "count"
        bulk = getattr(dec, "bulk", None)
        ucodes = uniq.tolist()
        vals = bulk(ucodes) if bulk else [dec(c) for c in ucodes]
        ents = [{keyname: v, cname: n}
                for v, n in zip(vals, counts.tolist())]
        # identical ordering contract to the general path: sort by
        # the str() of the 1-key tuple — skipped when np.unique's
        # byte order already IS that order (safe-ASCII payloads)
        if not getattr(dec, "byte_ordered", False):
            ents.sort(key=lambda e: str((e[keyname],)))
        return {"@groupby": ents}

    def _bind_groupby_vars(self, gq: GraphQuery, dest: np.ndarray):
        """`a as count(uid)` / `m as max(val(x))` inside a groupby block
        binds a value var keyed by the group's uid — only legal when
        grouping by exactly one uid predicate (ref groupby.go:118
        "can only use UID predicate with groupby" for var assignment).
        Aggregated across every parent's edge set (dest union), like
        the reference's var groupby over the whole block."""
        var_children = [c for c in gq.children if c.var]
        if not var_children:
            return
        tab0 = self._tablet(gq.groupby[0].attr) if gq.groupby else None
        if len(gq.groupby) != 1 or tab0 is None or \
                tab0.schema.value_type != TypeID.UID:
            raise GQLError(
                "assigning a groupby result to a variable needs exactly "
                "one uid predicate in @groupby")
        groups = self._groupby_groups(gq, dest)
        for cgq in var_children:
            vmap: dict[int, Val] = {}
            for key, members in groups.items():
                guid = int(key[0], 0)
                if cgq.attr == "uid" and cgq.is_count:
                    vmap[guid] = Val(TypeID.INT, len(members))
                elif cgq.agg_func and cgq.needs_var:
                    src = self.value_vars.get(cgq.needs_var[0].name, {})
                    agg = _agg_members(cgq.agg_func, src, members)
                    if agg is not None:
                        vmap[guid] = agg
                elif cgq.agg_func and cgq.agg_pred:
                    agg = self._agg_pred_members(cgq, members)
                    if agg is not None:
                        vmap[guid] = agg
            self.value_vars[cgq.var] = vmap

    def _recurse_scalar_cache(self, node: ExecNode) -> dict:
        """uid -> json value maps for every flat scalar child of a
        @recurse block, gathered columnarly over the WHOLE visited uid
        set once — the per-node get_postings walk dominated the q067
        profile (one posting fetch per node per scalar pred across
        ~10k visited nodes). Keys = (attr, langs); ineligible children
        (lang fans, lists, vars, facets) stay on the exact path."""
        parts = [node.dest]
        for lv in node.recurse_levels:
            for per_parent in lv.values():
                parts.extend(per_parent.values())
        parts = [p for p in parts if len(p)]
        if not parts:
            return {}
        allu = np.unique(np.concatenate(parts))
        cache: dict = {}
        seen: set = set()
        levels = node.recurse_preds or [node.gq.children]
        for preds in levels:
            for cgq in preds:
                tab = self._tablet(cgq.attr.lstrip("~"))
                if tab is None \
                        or tab.schema.value_type == TypeID.UID:
                    continue
                key = (cgq.attr, tuple(cgq.langs or ()))
                if key in seen:
                    continue
                seen.add(key)
                cm = self._colvals_for_emit(tab, cgq, allu)
                if cm is not None:
                    cache[key] = cm
        return cache

    def _emit_recurse_node(self, node: ExecNode, uid: int, level: int
                           ) -> dict:
        # uid appears only when the block asks for it (ref
        # query3_test.go TestRecurseQuery vs TestRecurseQueryLimitDepth2)
        obj: dict[str, Any] = {}
        if any(c.attr == "uid" and not c.is_count
               for c in node.gq.children):
            obj["uid"] = hex(uid)
        # per-level resolved children (expand() differs by level); the
        # deepest nodes reuse the last level's resolution for scalars
        if node.recurse_preds:
            children = node.recurse_preds[
                min(level, len(node.recurse_preds) - 1)]
        else:
            children = node.gq.children
        # value/scalar children at every level
        for cgq in children:
            tab = self._tablet(cgq.attr.lstrip("~"))
            if tab is None:
                continue
            name = cgq.alias or cgq.attr
            if tab.schema.value_type != TypeID.UID:
                cm = getattr(self, "_recurse_colvals", {}).get(
                    (cgq.attr, tuple(cgq.langs or ())))
                if cm is not None:
                    v = cm.get(uid)
                    if v is not None:
                        obj[name] = v
                    continue
                ps = tab.get_postings(uid, self.read_ts)
                if cgq.langs == ["*"]:
                    for p in ps:
                        key = f"{cgq.attr}@{p.lang}" if p.lang \
                            else cgq.attr
                        obj[key] = to_json_value(self._typed(tab, p))
                    continue
                sel = self._select_posting(ps, cgq.langs)
                if sel is not None:
                    obj[name] = to_json_value(self._typed(tab, sel))
        if level < len(node.recurse_levels):
            lv = node.recurse_levels[level]
            for cgq in children:
                attr = cgq.attr
                per_parent = lv.get(attr)
                if not per_parent or uid not in per_parent:
                    continue
                name = cgq.alias or attr
                kids = [k for k in
                        (self._emit_recurse_node(node, int(d),
                                                 level + 1)
                         for d in self._order_paginate(
                             cgq, per_parent[uid]).tolist())
                        if k]  # empty nodes drop (TestRecurseQuery:
                #                the nameless friend never appears)
                if kids:
                    obj[name] = kids
        return obj

    def _emit_paths(self, node: ExecNode) -> list:
        """_path_ emission: the NESTED chain keyed by each hop's
        traversed predicate, facet weight as `pred|key` on the hop's
        child object (ref query/outputnode.go shortest-path subgraph +
        query3_test.go TestKShortestPathWeighted shape)."""
        out = []
        weights = node.path_weights or [None] * len(node.path_nodes)
        specs = getattr(node, "path_specs", None) or []
        for path, w in zip(node.path_nodes, weights):
            if not path:
                continue
            tree: dict[str, Any] = {"uid": hex(path[0])}
            if w is not None:
                # the reference renders weights %f-style (6 places), so
                # an accumulated 0.30000000000000004 reads back as 0.3
                tree["_weight_"] = float(f"{w:.6f}")
            cur = tree
            for u, v in zip(path, path[1:]):
                hop = None
                for attr, tab, rev, wkey in specs:
                    get = tab.get_reverse_uids if rev \
                        else tab.get_dst_uids
                    ds = get(int(u), self.read_ts)
                    if np.any(ds == v):
                        hop = (attr, tab, rev, wkey)
                        break
                child: dict[str, Any] = {"uid": hex(int(v))}
                if hop is None:
                    cur["path"] = child
                else:
                    attr, tab, rev, wkey = hop
                    cur[attr] = child
                    if wkey:
                        fsrc, fdst = (int(v), int(u)) if rev \
                            else (int(u), int(v))
                        fv = tab.get_facets(
                            fsrc, fdst, self.read_ts).get(wkey)
                        if fv is not None:
                            child[f"{attr}|{wkey}"] = to_json_value(fv)
                cur = child
            out.append(tree)
        return out

    def _normalize(self, obj: dict) -> list[dict]:
        """@normalize: flatten nesting into one row per LEAF PATH —
        the cartesian merge of each child list's flattened rows with
        the parent's scalars (ref outputnode.go:325 normalize's
        parentSlice x childSlice merge). A parent with two friends
        yields two flat rows, never one merged-overwritten object."""
        rows: list[dict] = [{k: v for k, v in obj.items()
                             if k != "uid" and not isinstance(v, dict)
                             and not (isinstance(v, list) and v
                                      and isinstance(v[0], dict))}]
        for k, v in obj.items():
            if isinstance(v, dict):
                child_rows = self._normalize(v)
            elif isinstance(v, list) and v and isinstance(v[0], dict):
                child_rows = [r for item in v
                              for r in self._normalize(item)]
            else:
                continue
            if child_rows:
                rows = [{**r, **c} for r in rows for c in child_rows]
        return rows


class Agg:
    __slots__ = ("kind", "value")

    def __init__(self, kind, value):
        self.kind = kind
        self.value = value


def _cmp(op: str, a, b) -> bool:
    # one comparator table for scalar and vector paths (_CMP_VEC) —
    # they had drifted once already (review finding)
    fn = _CMP_VEC.get(op)
    if fn is None:
        raise GQLError(f"bad comparison {op}")
    return fn(a, b)


def _agg_members(fn: str, vmap, members: list[int]) -> Optional[Val]:
    """Aggregate a value var over one group's member uids — columnar
    vars use one searchsorted gather in member order (the dict path's
    iteration order, so float-sum rounding is unchanged)."""
    if isinstance(vmap, ColVar):
        m = np.asarray(members, dtype=np.uint64)
        _u, vals = vmap.gather(m)
        return _aggregate_col(fn, vals, vmap)
    vals = [vmap[u] for u in members if u in vmap]
    return _aggregate(fn, vals)


def _internal_values(vmap, src: np.ndarray, kind: str) -> dict:
    """node.values for a val()/math node.  Emission only ever reads the
    block's own uids, so a columnar var materializes Vals for src
    alone — not its whole (possibly 21M-scale) domain."""
    if isinstance(vmap, ColVar) and src is not None and len(src):
        # materialize per ROW at emission, not per domain here: the
        # block may paginate 1M var rows down to a handful (q046).
        # src arrives in EMISSION order (post-sort) — the lazy map's
        # lookups need an ascending domain
        return _ColAggVals(vmap.take(np.sort(src)), kind)
    return {u: [Agg(kind, v)] for u, v in vmap.items()}


class _ColAggVals(Mapping):
    """node.values view over a ColVar subset: each emitted row
    materializes its [Agg(Val)] on demand; exact object columns
    (datetime vars) bypass the lossy float domain."""

    __slots__ = ("sub", "kind")

    def __init__(self, sub: ColVar, kind: str):
        self.sub = sub
        self.kind = kind

    def __len__(self):
        return len(self.sub.uids)

    def __iter__(self):
        return iter(self.sub.uids.tolist())

    def __contains__(self, u):
        arr = self.sub.uids
        i = int(np.searchsorted(arr, np.uint64(u)))
        return i < len(arr) and int(arr[i]) == int(u)

    def get(self, u, default=None):
        arr = self.sub.uids
        i = int(np.searchsorted(arr, np.uint64(u)))
        if i >= len(arr) or int(arr[i]) != int(u):
            return default
        if self.sub.objs is not None:
            v = Val(self.sub.tid, self.sub.objs[i])
        else:
            v = self.sub.to_val(self.sub.vals[i])
        return [Agg(self.kind, v)]

    def __getitem__(self, u):
        got = self.get(u)
        if got is None:
            raise KeyError(u)
        return got


def _aggregate_col(fn: str, arr: np.ndarray, cv: ColVar) -> Optional[Val]:
    """_aggregate over a gathered ColVar column — no Val materialization.
    Sum stays a sequential left fold over the python list (ints exact,
    float rounding identical to the dict path's committed goldens).
    Math-result vars (frac/isbool) keep per-element typing quirks by
    falling back to the Val path."""
    if not len(arr):
        return None
    if cv.frac or cv.isbool:
        return _aggregate(fn, [cv.to_val(x) for x in arr.tolist()])
    if cv.tid == TypeID.BOOL:
        if fn == "min":
            return Val(TypeID.BOOL, bool(arr.min()))
        if fn == "max":
            return Val(TypeID.BOOL, bool(arr.max()))
        return None  # sum/avg over bools: not numeric (dict-path parity)
    if fn == "min":
        return cv.to_val(arr[int(np.argmin(arr))])
    if fn == "max":
        return cv.to_val(arr[int(np.argmax(arr))])
    if fn == "sum":
        s = sum(arr.tolist())
        return Val(TypeID.INT if isinstance(s, int) else TypeID.FLOAT, s)
    if fn == "avg":
        return Val(TypeID.FLOAT, sum(arr.tolist()) / len(arr))
    return None


def _aggregate(fn: str, vals: list[Val]) -> Optional[Val]:
    # uniform numeric fast path: one numpy reduction instead of a
    # per-element sort_key() python loop (q020 at the 21M regime spends
    # ~half its time here otherwise; ref query/aggregator.go works on
    # typed scalars the same way)
    if vals:
        t0 = vals[0].tid
        if t0 in (TypeID.INT, TypeID.FLOAT) \
                and all(v.tid is t0 for v in vals):
            try:
                arr = np.asarray(
                    [v.value for v in vals],
                    np.int64 if t0 == TypeID.INT else np.float64)
            except (TypeError, ValueError, OverflowError):
                arr = None
            if arr is not None:
                if fn == "min":
                    return vals[int(np.argmin(arr))]
                if fn == "max":
                    return vals[int(np.argmax(arr))]
                if fn == "sum":
                    # sequential sum over the C-level list, NOT
                    # np.sum: ints must not wrap at int64, and
                    # numpy's pairwise float summation rounds
                    # differently than the committed goldens
                    return Val(t0, sum(arr.tolist()))
                if fn == "avg":
                    return Val(TypeID.FLOAT,
                               sum(arr.tolist()) / len(arr))
    nums = []
    for v in vals:
        if v.tid in (TypeID.INT, TypeID.FLOAT):
            nums.append(v.value)
        elif v.tid == TypeID.DATETIME:
            nums.append(v)
    if not vals:
        return None
    if fn in ("min", "max"):
        try:
            pick = (min if fn == "min" else max)(
                vals, key=lambda v: sort_key(v))
            return pick
        except ValueError:
            return None
    if not nums:
        return None
    plain = [n for n in nums if not isinstance(n, Val)]
    if not plain:
        return None
    if fn == "sum":
        s = sum(plain)
        return Val(TypeID.INT if isinstance(s, int) else TypeID.FLOAT, s)
    if fn == "avg":
        return Val(TypeID.FLOAT, sum(plain) / len(plain))
    return None


class _VecFallback(Exception):
    """Raised inside _eval_math_vec when a leaf or op needs the dict
    path (non-columnar var, datetime, exotic result)."""


def _eval_math_vec(tree, value_vars):
    """Columnar _eval_math: every var leaf is a ColVar, every op is a
    vector op over float64 — the same domain the dict path works in
    (its leaves go through float()).  N-ary ops align operands by
    intersecting uid arrays; per-element failure semantics (div by
    zero, sqrt of negative, log of nonpositive drop the uid) are
    reproduced with masks or per-element maps.  Returns a ColVar, or
    None for an all-constant tree (dict-path parity: no per-uid map)."""
    import math as _m
    import time as _time

    # Array nodes are (uids, float64 vals, isbool).  Bool-ness is a
    # FLAG, never a dtype: the dict path's python bools act as 0/1
    # ints inside arithmetic (True+True == 2) but materialize as BOOL
    # when they survive to the top — numpy bool arrays would instead
    # do logical arithmetic (True+True == True), so comparisons store
    # 0.0/1.0 and carry the flag.

    # float64 is the working domain — bail to the exact dict path
    # whenever int semantics are observable: int columns beyond 2^53,
    # or an int/int division/mod (integral + truncating in the
    # reference's int64 arm, math.go applyArith; float division would
    # both misdivide and misround)
    def _int_exactness_check(t) -> tuple[bool, float]:
        """(is_int, max-abs bound) for subtree t; raises _VecFallback
        when int RESULTS could leave float64's exact range (not just
        inputs — f*f of two in-range ints overflows 2^53) or an
        int/int division needs the exact truncating arm."""
        if t.const is not None:
            isint = isinstance(t.const, int)
            if isint and abs(t.const) >= 2 ** 53:
                raise _VecFallback
            return isint, float(abs(t.const))
        if t.var:
            cv = value_vars.get(t.var)
            if isinstance(cv, ColVar) and cv.tid == TypeID.INT:
                b = float(np.abs(cv.vals).max()) if len(cv.vals) \
                    else 0.0
                if b >= 2.0 ** 53:
                    raise _VecFallback
                return True, b
            return False, 0.0
        subs = [_int_exactness_check(c) for c in t.children]
        if t.fn == "cond":
            # the RESULT is one of the branches — the boolean
            # condition child never contributes int-ness or bounds
            subs = subs[1:]
        ints = bool(subs) and all(i for i, _ in subs)
        bounds = [b for _, b in subs]
        if t.fn in ("/", "%") and ints:
            raise _VecFallback
        if not ints:
            return False, 0.0
        if t.fn in ("+", "-"):
            b = sum(bounds)
        elif t.fn == "*":
            b = 1.0
            for x in bounds:
                b *= max(x, 1.0)
        elif t.fn in ("min", "max", "cond"):
            b = max(bounds) if bounds else 0.0
        else:
            return False, 0.0
        if b >= 2.0 ** 53:
            raise _VecFallback
        return True, b

    _int_exactness_check(tree)

    def align(args):
        """Align array-arg uid domains; broadcast consts. Mismatched
        domains need the dict path's union-with-zero semantics
        (ref query/math.go:73) — bail rather than intersect."""
        arrs = [a for a in args if not isinstance(a, float)]
        uids = arrs[0][0]
        for a in arrs[1:]:
            if len(a[0]) != len(uids) \
                    or not np.array_equal(a[0], uids):
                raise _VecFallback
        out = []
        for a in args:
            if isinstance(a, float):
                out.append(np.full(len(uids), a))
            else:
                pos = np.searchsorted(a[0], uids)
                out.append(a[1][pos])
        return uids, out

    def map1(fn, uids, x):
        ou, ov = [], []
        for u, xv in zip(uids.tolist(), x.tolist()):
            try:
                ov.append(float(fn(xv)))
            except (ZeroDivisionError, ValueError):
                continue
            ou.append(u)
        return (np.asarray(ou, np.uint64),
                np.asarray(ov, np.float64), False)

    def eval_node(t):
        if t.const is not None:
            return float(t.const)
        if t.var:
            cv = value_vars.get(t.var)
            if cv is None:
                return (np.asarray([], np.uint64),
                        np.asarray([], np.float64), False)
            if not isinstance(cv, ColVar):
                raise _VecFallback
            return (cv.uids, cv.floats(), False)
        args = [eval_node(c) for c in t.children]
        if all(isinstance(a, float) for a in args):
            raise _VecFallback  # constant subtree feeding per-uid ops:
            # keep the dict path's scalar folding exactly
        flags = [a[2] if not isinstance(a, float) else False
                 for a in args]
        uids, asarr = align(args)
        fn = t.fn
        if fn == "+":
            return uids, asarr[0] + asarr[1], False
        if fn == "-":
            return (uids, asarr[0] - asarr[1], False) \
                if len(asarr) == 2 else (uids, -asarr[0], False)
        if fn == "*":
            return uids, asarr[0] * asarr[1], False
        if fn in ("/", "%"):
            keep = asarr[1] != 0.0
            u2, a, b = uids[keep], asarr[0][keep], asarr[1][keep]
            return u2, (a / b if fn == "/" else np.mod(a, b)), False
        if fn in ("<", ">", "<=", ">=", "==", "!="):
            r = {"<": np.less, ">": np.greater, "<=": np.less_equal,
                 ">=": np.greater_equal, "==": np.equal,
                 "!=": np.not_equal}[fn](asarr[0], asarr[1])
            return uids, r.astype(np.float64), True
        if fn == "cond":
            # the result is one of the BRANCHES, so only their flags
            # matter; mixed bool/number branches would need a
            # per-element flag — dict path handles those
            bflags = flags[1:]
            if any(bflags) and not all(bflags):
                raise _VecFallback
            r = np.where(asarr[0] != 0, asarr[1], asarr[2])
            return uids, r, all(bflags)
        if fn in ("min", "max"):
            # python min/max RETURN one operand, so a bool operand can
            # surface element-wise; only uniform flags are
            # representable with one flag
            if any(flags) and not all(flags):
                raise _VecFallback
            r = asarr[0]
            red = np.minimum if fn == "min" else np.maximum
            for x in asarr[1:]:
                r = red(r, x)
            return uids, r, all(flags)
        if fn == "floor":
            return uids, np.floor(asarr[0]), False
        if fn == "ceil":
            return uids, np.ceil(asarr[0]), False
        if fn == "sqrt":
            # math.sqrt raises only for NEGATIVE args; NaN passes
            # through as NaN and keeps its uid
            keep = ~(asarr[0] < 0.0)
            return uids[keep], np.sqrt(asarr[0][keep]), False
        # transcendental / two-arg host funcs: per-element math.* calls
        # for bit-parity with the dict path (numpy's vectorized exp/log
        # can differ in the last ulp)
        if fn == "exp":
            return map1(_m.exp, uids, asarr[0])
        if fn == "ln":
            return map1(_m.log, uids, asarr[0])
        if fn == "sigmoid":
            return map1(lambda x: 1.0 / (1.0 + _m.exp(-x)),
                        uids, asarr[0])
        if fn == "since":
            # wall clock by SEMANTICS: since() measures from an
            # epoch-seconds datetime value (ref applySince)
            now = _time.time()  # dglint: disable=DG06
            return uids, now - asarr[0], False
        if fn in ("pow", "logbase"):
            xs, ys = asarr[0].tolist(), asarr[1].tolist()
            ou, ov = [], []
            op = (lambda x, y: x ** y) if fn == "pow" else _m.log
            for u, xv, yv in zip(uids.tolist(), xs, ys):
                try:
                    # complex pow results raise TypeError at float()
                    # and must propagate to the dict-path fallback,
                    # which keeps the uid (historical behavior)
                    ov.append(float(op(xv, yv)))
                except (ZeroDivisionError, ValueError):
                    continue
                ou.append(u)
            return (np.asarray(ou, np.uint64),
                    np.asarray(ov, np.float64), False)
        raise _VecFallback  # op the vector path doesn't cover

    res = eval_node(tree)
    if isinstance(res, float):
        return None
    uids, vals, isbool = res
    if isbool:
        return ColVar(uids, vals.astype(np.uint8), TypeID.FLOAT,
                      isbool=True)
    return ColVar(uids, vals.astype(np.float64), TypeID.FLOAT,
                  frac=True)


def _merge_list_key(obj: dict, name: str, items: list):
    """Repeated child attrs share one output key, merged in child
    order (ref query0:TestGroupBy_RepeatAttr: a @groupby friend and a
    plain friend both land under \"friend\"); a prior single-object
    occupant joins the list rather than being dropped."""
    prev = obj.get(name)
    if isinstance(prev, list):
        obj[name] = prev + items
    elif name in obj:
        obj[name] = [prev] + items
    else:
        obj[name] = items


def _join_codes(u_sorted: np.ndarray, codes: np.ndarray,
                rows: np.ndarray
                ) -> Optional[tuple[np.ndarray, np.ndarray]]:
    """Join group members against one key column: for every row uid,
    gather EVERY aligned code (multi-valued attrs fan out). Returns
    (rep, gathered) where rep repeats each row index once per matched
    code and gathered holds the codes; None when nothing matches."""
    starts = np.searchsorted(u_sorted, rows, "left")
    ends = np.searchsorted(u_sorted, rows, "right")
    cnt = (ends - starts).astype(np.int64)
    total = int(cnt.sum())
    if total == 0:
        return None
    rep = np.repeat(np.arange(len(rows)), cnt)
    # gathered indices = starts[row] + position-within-row
    base = np.repeat(starts, cnt)
    csum = np.concatenate(([0], np.cumsum(cnt)[:-1]))
    inner = np.arange(total) - np.repeat(csum, cnt)
    return rep, codes[base + inner]


def _math_tree_vars(tree):
    """Every var name a math tree reads."""
    if tree.var:
        yield tree.var
    for c in tree.children:
        yield from _math_tree_vars(c)


def _eval_math(tree, value_vars, src=None) -> "dict[int, Val] | ColVar":
    """Per-uid math over value vars (ref query/math.go:213 processBinary).
    Tries the columnar path first; falls back to the per-uid dict walk
    when a var isn't columnar or an op needs scalar semantics. An
    ALL-CONSTANT expression broadcasts over the enclosing block's uids
    (ref query0_test.go TestQueryConstMathVal: `a as math(24/8 * 3)`
    binds 9 for every root uid)."""
    import math as _m

    def const_map(x):
        if src is None or not len(src):
            return {}
        if isinstance(x, int) and not isinstance(x, bool):
            v = Val(TypeID.INT, x)  # exact at any magnitude
        elif float(x).is_integer() and abs(x) < 2**53:
            v = Val(TypeID.INT, int(x))
        else:
            v = Val(TypeID.FLOAT, float(x))
        return {int(u): v for u in src.tolist()}

    try:
        cv = _eval_math_vec(tree, value_vars)
        if cv is not None:
            return cv
        # None = all-constant tree: fall through so the dict path
        # folds the scalar and broadcasts it
    except _VecFallback:
        pass
    except (TypeError, OverflowError):
        # exotic per-element results (complex pow, overflow) — let the
        # dict path produce its exact historical behavior
        pass

    def eval_node(t) -> dict[int, float] | float:
        if t.const is not None:
            # int literals stay ints (exact arithmetic + the int/int
            # division arm); everything else is float64
            return t.const if isinstance(t.const, int) \
                else float(t.const)
        if t.var:
            vmap = value_vars.get(t.var, {})
            # datetimes flow as epoch-seconds floats so since() and
            # date comparisons work (ref aggregator.go applySince
            # converts datetime -> float seconds); INT values stay
            # python ints — the int/int arithmetic arm must be exact
            # beyond 2^53 and divide integrally (ref math.go int64
            # arm; query4:TestBigMathValue/TestFloatConverstion)
            return {u: (v.value.timestamp()
                        if v.tid == TypeID.DATETIME
                        else int(v.value) if v.tid == TypeID.INT
                        else float(v.value))
                    for u, v in vmap.items()
                    if v.tid in (TypeID.INT, TypeID.FLOAT, TypeID.BOOL,
                                 TypeID.DATETIME)}
        args = [eval_node(c) for c in t.children]
        fn = t.fn
        dicts = [a for a in args if isinstance(a, dict)]
        if not dicts:
            # all-constant expression
            return _apply_math(fn, list(args), _m)
        out = {}
        if fn in ("<", ">", "<=", ">=", "==", "!="):
            # comparisons iterate the LEFT operand's domain; a uid the
            # right map misses compares against zero (ref
            # query/math.go:147 processBinaryBoolean srcMap loop)
            left, right = args[0], args[1]
            if not isinstance(left, dict):
                return {}
            for u, lv in left.items():
                rv = right.get(u, 0.0) if isinstance(right, dict) \
                    else right
                try:
                    out[u] = _apply_math(fn, [lv, rv], _m)
                except (ZeroDivisionError, ValueError):
                    continue
            return out
        if fn == "cond":
            cond = args[0]
            if not isinstance(cond, dict):
                return {}
            for u, cv in cond.items():
                branch = args[1] if cv else args[2]
                out[u] = branch.get(u, 0.0) \
                    if isinstance(branch, dict) else branch
            return out
        # arithmetic / min / max / unary: the UNION of the operand
        # domains, zero-filling a side that misses the uid (ref
        # query/math.go:73 processBinary iterating mpr then mpl)
        uids = set()
        for a in dicts:
            uids |= set(a)
        for u in uids:
            vals = [a.get(u, 0.0) if isinstance(a, dict) else a
                    for a in args]
            try:
                out[u] = _apply_math(fn, vals, _m)
            except (ZeroDivisionError, ValueError):
                continue
        return out

    res = eval_node(tree)
    if not isinstance(res, dict):
        if isinstance(res, (int, float)) and not isinstance(res, bool):
            return const_map(res)
        return {}
    out = {}
    for u, x in res.items():
        if isinstance(x, bool):
            out[u] = Val(TypeID.BOOL, x)
        elif isinstance(x, int):
            # exact int arithmetic result (any magnitude)
            out[u] = Val(TypeID.INT, x)
        elif isinstance(x, float) and x.is_integer() and abs(x) < 2**53:
            out[u] = Val(TypeID.INT, int(x))
        else:
            out[u] = Val(TypeID.FLOAT, x)
    return out


def _trunc_div(a: int, b: int) -> int:
    """Go's int64 division truncates toward zero; python's // floors."""
    q = a // b
    if q < 0 and q * b != a:
        q += 1
    return q


def _apply_math(fn: str, v: list, _m):
    both_int = len(v) == 2 \
        and isinstance(v[0], int) and not isinstance(v[0], bool) \
        and isinstance(v[1], int) and not isinstance(v[1], bool)
    if fn == "+":
        return v[0] + v[1]
    if fn == "-":
        return v[0] - v[1] if len(v) == 2 else -v[0]
    if fn == "*":
        return v[0] * v[1]
    if fn == "/":
        if both_int:
            # int/int divides INTEGRALLY and exactly (ref math.go
            # applyArith int64 arm; query4:TestBigMathValue)
            return _trunc_div(v[0], v[1])
        return v[0] / v[1]
    if fn == "%":
        if both_int:
            return v[0] - _trunc_div(v[0], v[1]) * v[1]
        return v[0] % v[1]
    if fn == "<":
        return v[0] < v[1]
    if fn == ">":
        return v[0] > v[1]
    if fn == "<=":
        return v[0] <= v[1]
    if fn == ">=":
        return v[0] >= v[1]
    if fn == "==":
        return v[0] == v[1]
    if fn == "!=":
        return v[0] != v[1]
    if fn == "min":
        return min(v)
    if fn == "max":
        return max(v)
    if fn == "exp":
        return _m.exp(v[0])
    if fn == "ln":
        return _m.log(v[0])
    if fn == "sqrt":
        return _m.sqrt(v[0])
    if fn == "floor":
        return float(_m.floor(v[0]))
    if fn == "ceil":
        return float(_m.ceil(v[0]))
    if fn == "pow":
        # float domain like the reference's math.Pow — exact bigint
        # pow would happily materialize petabyte integers; overflow
        # drops the uid like the other per-element failures
        try:
            return float(v[0]) ** float(v[1])
        except OverflowError:
            raise ValueError("math: pow overflow")
    if fn == "logbase":
        return _m.log(v[0], v[1])
    if fn == "sigmoid":
        return 1.0 / (1.0 + _m.exp(-v[0]))
    if fn == "cond":
        return v[1] if v[0] else v[2]
    if fn == "since":
        # ref query/aggregator.go:353 applySince: seconds elapsed since
        # the datetime (datetimes reach math as epoch-seconds floats)
        import time as _time
        # wall clock by SEMANTICS (epoch-seconds argument)
        return _time.time() - v[0]  # dglint: disable=DG06
    raise GQLError(f"math op {fn!r} not supported")


def _levenshtein(a: str, b: str, cap: int) -> int:
    """Banded edit distance (ref worker/match.go levenshtein).
    Dispatches to the native C++ kernel (native/native.cc
    dgt_levenshtein) when built."""
    from dgraph_tpu import native
    if native.available():
        return native.levenshtein(a, b, cap)
    if abs(len(a) - len(b)) > cap:
        return cap + 1
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i]
        lo = cap + 1
        for j, cb in enumerate(b, 1):
            c = min(prev[j] + 1, cur[j - 1] + 1,
                    prev[j - 1] + (ca != cb))
            cur.append(c)
            lo = min(lo, c)
        if lo > cap:
            return cap + 1
        prev = cur
    return prev[-1]
