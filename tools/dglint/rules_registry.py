"""DG08/DG14 — declarative registries: observability names and typed
wire errors.

Observability names are API: a typo'd metric name silently forks a
time series nobody's dashboard reads, a failpoint site that production
code never fires turns a chaos test into a no-op, and a typo'd span
name forks a trace nobody queries. The registries are declarative
tuples in their home modules —

    dgraph_tpu/utils/failpoint.py   SITES = ("transport.send", ...)
    dgraph_tpu/utils/metrics.py     REGISTERED = ("dgraph_num_...",)
    dgraph_tpu/utils/tracing.py     SPAN_NAMES = ("query", ...)

— and DG08 checks, across the whole tree, that every literal name
passed to `failpoint.fire(...)` / `inc_counter` / `set_gauge` /
`observe` / `span(...)` is registered, and that no registry lists a
name twice. Dynamically computed names are skipped (the linter only
reads literals). Tests may arm ad-hoc fixture sites via
`failpoint.arm` and open ad-hoc spans; only the dgraph_tpu/ tree is
checked, and only when the span registry exists (fixture projects
without it skip the span check).

DG14 — typed-wire-error discipline. A typed error that loses either of
its wire halves silently degrades to a bare RuntimeError 500 at the
far edge — exactly the retry-contract bug the type exists to prevent.
The registry is `WIRE_ERRORS = (("Cls", "key"), ...)` in
dgraph_tpu/cluster/errors.py; DG14 checks that every typed error class
defined there is registered, that each registered (class, key) has a
serialization arm in cluster/service.py `_client_loop` (an
`except Cls` whose `resp` dict carries the key) and a client re-raise
in cluster/client.py `_unwrap` (a `resp.get(key)` / `resp[key]` probe
plus `raise Cls`), that neither side invents unregistered wire keys,
and that no class or key is listed twice.
"""

from __future__ import annotations

import ast
import os

from tools.dglint.astutil import call_name, str_const, walk_calls
from tools.dglint.core import (
    FileContext, Finding, ProjectContext, register, register_project,
)

# device_call (query/devicecall.py) takes a dispatch site's counter
# name first and increments it itself
_METRIC_FNS = frozenset({"inc_counter", "set_gauge", "observe",
                         "device_call"})
# span() and the conventional `from ...tracing import span as _span`
_SPAN_FNS = frozenset({"span", "_span"})

_FAILPOINT_HOME = "dgraph_tpu/utils/failpoint.py"
_METRICS_HOME = "dgraph_tpu/utils/metrics.py"
_TRACING_HOME = "dgraph_tpu/utils/tracing.py"


def parse_registry(tree: ast.AST, target: str):
    """Module-level `target = (...)` tuple/list/set/frozenset of str
    literals -> (names, [(dupe, lineno)]); (None, []) if absent."""
    for node in tree.body if isinstance(tree, ast.Module) else []:
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == target
                   for t in node.targets):
            continue
        value = node.value
        if isinstance(value, ast.Call) \
                and call_name(value) in ("frozenset", "set", "tuple") \
                and value.args:
            value = value.args[0]
        if not isinstance(value, (ast.Tuple, ast.List, ast.Set)):
            return None, []
        names: list[str] = []
        dupes: list[tuple[str, int]] = []
        for el in value.elts:
            s = str_const(el)
            if s is None:
                continue
            if s in names:
                dupes.append((s, getattr(el, "lineno", node.lineno)))
            names.append(s)
        return names, dupes
    return None, []


@register("DG08", "registry-discipline",
          scopes=("dgraph_tpu/",))
def check_registries(ctx: FileContext):
    """Every literal failpoint site fired and metric name emitted must
    appear in its registry tuple exactly once."""
    proj = ctx.project
    if not proj.registries_found:
        return
    if ctx.rel == _FAILPOINT_HOME:
        for name, line in proj.failpoint_dupes:
            yield ctx.finding(
                "DG08",
                _FakeNode(line),
                f"failpoint site {name!r} registered twice in SITES")
    if ctx.rel == _METRICS_HOME:
        for name, line in proj.metric_dupes:
            yield ctx.finding(
                "DG08",
                _FakeNode(line),
                f"metric {name!r} registered twice in REGISTERED")
    if ctx.rel == _TRACING_HOME:
        for name, line in proj.span_dupes:
            yield ctx.finding(
                "DG08",
                _FakeNode(line),
                f"span name {name!r} registered twice in SPAN_NAMES")
    for call in ctx.calls:
        name = call_name(call)
        if name is None or not call.args:
            continue
        parts = name.split(".")
        if parts[-1] == "fire" and len(parts) >= 2 \
                and parts[-2] == "failpoint":
            site = str_const(call.args[0])
            if site is not None \
                    and site not in proj.failpoint_sites:
                yield ctx.finding(
                    "DG08", call,
                    f"failpoint site {site!r} fired but not listed "
                    "in utils/failpoint.py SITES")
        elif parts[-1] in _METRIC_FNS:
            metric = str_const(call.args[0])
            if metric is not None \
                    and metric not in proj.metric_names:
                yield ctx.finding(
                    "DG08", call,
                    f"metric {metric!r} emitted but not listed in "
                    "utils/metrics.py REGISTERED")
        elif parts[-1] in _SPAN_FNS and proj.span_registry_found \
                and ctx.rel != _TRACING_HOME:
            sname = str_const(call.args[0])
            if sname is not None and sname not in proj.span_names:
                yield ctx.finding(
                    "DG08", call,
                    f"span name {sname!r} opened but not listed in "
                    "utils/tracing.py SPAN_NAMES")


class _FakeNode:
    """Line-only anchor for registry-home findings."""

    def __init__(self, lineno: int):
        self.lineno = lineno


# ------------------------------------------------- DG14: typed wire errors

_ERRORS_HOME = "dgraph_tpu/cluster/errors.py"
_SERVICE_HOME = "dgraph_tpu/cluster/service.py"
_CLIENT_HOME = "dgraph_tpu/cluster/client.py"

# Response keys the base protocol owns (serialized by _client_loop's
# generic arms, consumed by _unwrap's non-typed branches) — legal on
# the wire without a WIRE_ERRORS entry.
_PROTOCOL_KEYS = frozenset({
    "ok", "error", "leader", "retryable", "aborted",
    "deadline_expired", "result",
})


def _dg14_tree(proj: ProjectContext, rel: str):
    """AST for `rel`: the re-parsed tree when this pass has it, else a
    fresh parse from disk (--changed-only passes re-parse only the
    changed set, but DG14 must always see all three protocol files).
    Memoized in proj.cache; None when unavailable (fixture projects
    that do not model the wire protocol skip the rule)."""
    memo = proj.cache.setdefault("dg14_trees", {})
    if rel in memo:
        return memo[rel]
    tree = proj.files.get(rel)
    if tree is None and rel in proj.summaries:
        try:
            with open(os.path.join(proj.root, rel),
                      encoding="utf-8") as f:
                tree = ast.parse(f.read())
        except (OSError, SyntaxError):
            tree = None
    memo[rel] = tree
    return tree


def _dg14_line(proj: ProjectContext, rel: str, line: int) -> str:
    lines = proj.sources.get(rel)
    if lines and 1 <= line <= len(lines):
        return lines[line - 1].strip()
    return ""


def _parse_wire_errors(tree: ast.Module):
    """Module-level `WIRE_ERRORS = (("Cls", "key"), ...)` ->
    (entries [(cls, key, line)], dupes [(what, line)]); (None, [])
    when the registry is absent or malformed."""
    for node in tree.body:
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == "WIRE_ERRORS"
                   for t in node.targets):
            continue
        if not isinstance(node.value, (ast.Tuple, ast.List)):
            return None, []
        entries: list[tuple[str, str, int]] = []
        dupes: list[tuple[str, int]] = []
        seen_cls: set[str] = set()
        seen_key: set[str] = set()
        for el in node.value.elts:
            if not (isinstance(el, (ast.Tuple, ast.List))
                    and len(el.elts) == 2):
                continue
            cls = str_const(el.elts[0])
            key = str_const(el.elts[1])
            if cls is None or key is None:
                continue
            line = getattr(el, "lineno", node.lineno)
            if cls in seen_cls:
                dupes.append((f"class {cls!r}", line))
            if key in seen_key:
                dupes.append((f"wire key {key!r}", line))
            seen_cls.add(cls)
            seen_key.add(key)
            entries.append((cls, key, line))
        return entries, dupes
    return None, []


def _find_func(tree: ast.AST, name: str):
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    return None


def _handler_names(handler: ast.ExceptHandler) -> set[str]:
    """Bare class names an `except` arm catches (last attribute part
    for dotted references; empty for a bare `except:`)."""
    t = handler.type
    elts = t.elts if isinstance(t, ast.Tuple) else [t] if t else []
    names = set()
    for e in elts:
        if isinstance(e, ast.Name):
            names.add(e.id)
        elif isinstance(e, ast.Attribute):
            names.add(e.attr)
    return names


def _resp_dict_keys(body) -> list[tuple[str, int]]:
    """Top-level str keys of every dict literal assigned to the name
    `resp` within `body` (the wire-response construction idiom of
    _client_loop). Nested payload dicts are deliberately NOT scanned —
    their keys ("pred", "readTs", ...) are the typed error's own
    schema, not protocol-level response keys."""
    out: list[tuple[str, int]] = []
    for stmt in body:
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Assign):
                continue
            if not any(isinstance(t, ast.Name) and t.id == "resp"
                       for t in node.targets):
                continue
            if not isinstance(node.value, ast.Dict):
                continue
            for k in node.value.keys:
                s = str_const(k) if k is not None else None
                if s is not None:
                    out.append((s, getattr(k, "lineno", node.lineno)))
    return out


@register_project("DG14", "typed-wire-error-discipline")
def check_wire_errors(proj: ProjectContext):
    """Every typed error in cluster/errors.py must be registered in
    WIRE_ERRORS and carried across the wire whole: an `except` arm in
    service.py _client_loop serializing its key, and a matching
    `resp.get(key)` re-raise in client.py ClusterClient._unwrap.
    Unregistered top-level wire keys on either side are flagged too —
    an invented key is a typed error one half of the protocol cannot
    see."""
    etree = _dg14_tree(proj, _ERRORS_HOME)
    stree = _dg14_tree(proj, _SERVICE_HOME)
    ctree = _dg14_tree(proj, _CLIENT_HOME)
    if etree is None or stree is None or ctree is None:
        return

    entries, dupes = _parse_wire_errors(etree)
    if entries is None:
        yield Finding(
            "DG14", _ERRORS_HOME, 1,
            "cluster/errors.py defines typed wire errors but no "
            "module-level WIRE_ERRORS registry (a tuple of "
            '("ClassName", "wire_key") pairs)',
            _dg14_line(proj, _ERRORS_HOME, 1))
        return
    for what, line in dupes:
        yield Finding(
            "DG14", _ERRORS_HOME, line,
            f"{what} listed twice in WIRE_ERRORS — one entry per "
            "typed error, one wire key per entry",
            _dg14_line(proj, _ERRORS_HOME, line))

    reg_cls = {c for c, _k, _l in entries}
    reg_keys = {k for _c, k, _l in entries}
    legal_keys = _PROTOCOL_KEYS | reg_keys

    # every typed error class defined in the home module is registered
    class_lines = {}
    for node in etree.body:
        if isinstance(node, ast.ClassDef):
            class_lines[node.name] = node.lineno
            if node.name not in reg_cls:
                yield Finding(
                    "DG14", _ERRORS_HOME, node.lineno,
                    f"typed error `{node.name}` has no WIRE_ERRORS "
                    "entry — without one it crosses the wire as a "
                    "bare RuntimeError and the client retry contract "
                    "never sees it",
                    _dg14_line(proj, _ERRORS_HOME, node.lineno))
    # ...and every registered class exists
    for cls, _key, line in entries:
        if cls not in class_lines:
            yield Finding(
                "DG14", _ERRORS_HOME, line,
                f"WIRE_ERRORS lists {cls!r} but cluster/errors.py "
                "defines no such class",
                _dg14_line(proj, _ERRORS_HOME, line))

    # --- server half: _client_loop serialization arms
    loop = _find_func(stree, "_client_loop")
    if loop is None:
        yield Finding(
            "DG14", _SERVICE_HOME, 1,
            "cluster/service.py has no _client_loop — the typed-wire-"
            "error serialization point DG14 checks is gone",
            _dg14_line(proj, _SERVICE_HOME, 1))
    else:
        arm_keys: dict[str, set[str]] = {}
        for node in ast.walk(loop):
            if not isinstance(node, ast.ExceptHandler):
                continue
            caught = _handler_names(node)
            keys = {k for k, _l in _resp_dict_keys(node.body)}
            for name in caught:
                arm_keys.setdefault(name, set()).update(keys)
        for cls, key, _line in entries:
            if cls not in class_lines:
                continue
            got = arm_keys.get(cls)
            if got is None:
                yield Finding(
                    "DG14", _SERVICE_HOME, loop.lineno,
                    f"_client_loop has no `except {cls}` arm — the "
                    f"typed error degrades to the generic handler and "
                    f"the client never sees wire key {key!r}",
                    _dg14_line(proj, _SERVICE_HOME, loop.lineno))
            elif key not in got:
                yield Finding(
                    "DG14", _SERVICE_HOME, loop.lineno,
                    f"_client_loop's `except {cls}` arm does not set "
                    f"wire key {key!r} in its resp dict — the client "
                    "cannot re-raise it typed",
                    _dg14_line(proj, _SERVICE_HOME, loop.lineno))
        for key, line in _resp_dict_keys(loop.body):
            if key not in legal_keys:
                yield Finding(
                    "DG14", _SERVICE_HOME, line,
                    f"_client_loop serializes unregistered wire key "
                    f"{key!r} — add a WIRE_ERRORS entry (and an "
                    "_unwrap re-raise) or use a registered key",
                    _dg14_line(proj, _SERVICE_HOME, line))

    # --- client half: _unwrap re-raise branches
    unwrap = _find_func(ctree, "_unwrap")
    if unwrap is None:
        yield Finding(
            "DG14", _CLIENT_HOME, 1,
            "cluster/client.py has no _unwrap — the typed-wire-error "
            "re-raise point DG14 checks is gone",
            _dg14_line(proj, _CLIENT_HOME, 1))
        return
    probed: dict[str, int] = {}
    raised: set[str] = set()
    for node in ast.walk(unwrap):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "get" \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id == "resp" and node.args:
            key = str_const(node.args[0])
            if key is not None:
                probed.setdefault(key, node.lineno)
        elif isinstance(node, ast.Subscript) \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "resp":
            key = str_const(node.slice)
            if key is not None:
                probed.setdefault(key, node.lineno)
        elif isinstance(node, ast.Raise) \
                and isinstance(node.exc, ast.Call):
            f = node.exc.func
            if isinstance(f, ast.Name):
                raised.add(f.id)
            elif isinstance(f, ast.Attribute):
                raised.add(f.attr)
    for cls, key, _line in entries:
        if cls not in class_lines:
            continue
        if key not in probed:
            yield Finding(
                "DG14", _CLIENT_HOME, unwrap.lineno,
                f"_unwrap never probes resp.get({key!r}) — a typed "
                f"{cls} from the server degrades to the generic "
                "RuntimeError fallback on the client",
                _dg14_line(proj, _CLIENT_HOME, unwrap.lineno))
        elif cls not in raised:
            yield Finding(
                "DG14", _CLIENT_HOME, unwrap.lineno,
                f"_unwrap probes wire key {key!r} but never raises "
                f"{cls} — the re-raise half of the typed contract is "
                "missing",
                _dg14_line(proj, _CLIENT_HOME, unwrap.lineno))
    for key, line in probed.items():
        if key not in legal_keys:
            yield Finding(
                "DG14", _CLIENT_HOME, line,
                f"_unwrap probes unregistered wire key {key!r} — "
                "no server arm serializes it; register it in "
                "WIRE_ERRORS or drop the branch",
                _dg14_line(proj, _CLIENT_HOME, line))
