"""Registry-vs-emission audit: dglint DG08 proves every literal
metric emission is REGISTERED; this is the converse — every name in
metrics.REGISTERED (and every failpoint SITE) must have at least one
literal emission site in the tree. A registered-but-never-emitted
name is a dead dashboard series (or a chaos seam production never
fires): it passes every runtime test while lying to operators."""

import ast
import os

from dgraph_tpu.utils import failpoint, metrics

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# device_call (query/devicecall.py) increments the counter it is named;
# watch_gauge names a gauge that a scrape reads from its owner
_EMITTERS = {"inc_counter", "set_gauge", "observe", "get_counter",
             "device_call", "watch_gauge"}


def _py_files():
    for root, dirs, files in os.walk(os.path.join(_REPO,
                                                  "dgraph_tpu")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(root, f)


def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _scan():
    """(metric names, failpoint sites) with >=1 literal call site."""
    emitted, fired = set(), set()
    for path in _py_files():
        with open(path) as fh:
            tree = ast.parse(fh.read(), filename=path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            arg0 = node.args[0]
            if not (isinstance(arg0, ast.Constant)
                    and isinstance(arg0.value, str)):
                continue
            name = _call_name(node)
            if name in _EMITTERS:
                emitted.add(arg0.value)
            elif name == "fire":
                fired.add(arg0.value)
    return emitted, fired


def test_every_registered_metric_is_emitted_somewhere():
    emitted, _ = _scan()
    dead = [n for n in metrics.REGISTERED if n not in emitted]
    assert not dead, (
        "REGISTERED metrics with no literal emission site "
        f"(dead series): {dead}")


def test_every_failpoint_site_is_fired_somewhere():
    _, fired = _scan()
    dead = [s for s in failpoint.SITES if s not in fired]
    assert not dead, (
        f"failpoint SITES never fired in production code: {dead}")


def test_registries_are_unique():
    assert len(set(metrics.REGISTERED)) == len(metrics.REGISTERED)
    assert len(set(failpoint.SITES)) == len(failpoint.SITES)
