"""Start-up rules: where the compile cache lives, that a failing or
missing device is loud, that host-only processes stay off the device,
and that chip_smoke.py's parent and its checks behave.
"""

import ast
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

import jax

from dgraph_tpu.engine import db as dbmod
from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.utils import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _py_files():
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d != "__pycache__"]
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(root, f)


# -- §5: one compile-cache rule ----------------------------------------


@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_cache_dir_from_env_sets_nothing(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert backend.configure_compile_cache() == "/some/dir"
    assert "jax_compilation_cache_dir" not in dict(config_updates)


def test_cache_dir_default_is_fixed_checkout_path(monkeypatch,
                                                  config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert backend.configure_compile_cache() == want
    got = dict(config_updates)
    assert got["jax_compilation_cache_dir"] == want
    # sub-second stage executables must not be kept out of the cache
    assert got["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_cache_is_configured_in_one_place():
    """Only utils/backend.py sets the cache option; only cli.main,
    the two root-level chip scripts that touch jax themselves
    (bench_micro.py, tools/mesh_smoke.py) and tests/conftest.py call
    the helper."""
    names, callers = [], []
    for path in _py_files():
        rel = os.path.relpath(path, REPO)
        if rel == os.path.join("tests", "test_backend.py"):
            continue
        src = open(path).read()
        if re.search(r"update\(\s*[\"']jax_compilation_cache_dir", src):
            names.append(rel)
        if re.search(r"\bconfigure_compile_cache\(\)", src):
            callers.append(rel)
    assert names == [os.path.join("dgraph_tpu", "utils", "backend.py")]
    assert sorted(callers) == sorted([
        "bench_micro.py", os.path.join("dgraph_tpu", "cli.py"),
        os.path.join("dgraph_tpu", "utils", "backend.py"),
        os.path.join("tests", "conftest.py"),
        os.path.join("tools", "mesh_smoke.py")])


# -- §3: no fallback that hides the device -----------------------------


def test_device_probe_errors_propagate(monkeypatch):
    def boom(*_a, **_k):
        raise RuntimeError("backend init failed")

    db = GraphDB()
    monkeypatch.setattr(dbmod, "_IS_ACCELERATOR", None)
    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="backend init failed"):
        db.device_is_accelerator()

    from dgraph_tpu.query import plan
    monkeypatch.setattr(dbmod, "_DISPATCH_SECONDS", None)
    monkeypatch.setattr(plan, "jit_stage", lambda *_a, **_k: boom)
    with pytest.raises(RuntimeError, match="backend init failed"):
        db.device_dispatch_seconds()
    assert dbmod._DISPATCH_SECONDS is None  # no 0.0 "device is free"


def test_cpu_backend_nobody_asked_for_is_an_error(monkeypatch):
    assert backend.require_devices()[0].platform == "cpu"  # asked for
    monkeypatch.setattr(backend, "cpu_requested", lambda: False)
    with pytest.raises(backend.NoAcceleratorError):
        backend.require_devices()


def test_bench_scripts_do_not_exit_zero_from_handlers():
    for name in ("chip_smoke.py", "bench_micro.py",
                 os.path.join("benchmark", "run.py")):
        tree = ast.parse(open(os.path.join(REPO, name)).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ExceptHandler):
                for call in ast.walk(node):
                    if isinstance(call, ast.Call) \
                            and ast.unparse(call.func) == "sys.exit":
                        assert ast.unparse(call) != "sys.exit(0)", name


# -- §4: one process per chip ------------------------------------------


def test_host_only_imports_leave_the_device_alone():
    """bulk / zero / backup / bench parents import these; none may
    initialize the backend (that is what takes the chip)."""
    code = (
        "import pkgutil, importlib, dgraph_tpu\n"
        "for m in pkgutil.walk_packages(dgraph_tpu.__path__, "
        "'dgraph_tpu.'):\n"
        "    if not m.name.endswith('__main__'):\n"
        "        importlib.import_module(m.name)\n"
        "import bench_micro\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env=dict(os.environ, PYTHONPATH=REPO),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_spawned_cluster_nodes_are_pinned_to_cpu():
    src = open(os.path.join(REPO, "dgraph_tpu", "bench",
                            "spawn.py")).read()
    assert 'JAX_PLATFORMS="cpu"' in src


# -- chip_smoke.py -----------------------------------------------------


def _imports(path):
    out = set()
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            out |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            out.add(node.module.split(".")[0])
    return out


def test_smoke_parent_imports_neither_jax_nor_dgraph_tpu():
    """Anywhere in the file, function bodies included — and the same
    for the two pure modules it borrows from tests/golden."""
    for rel in ("chip_smoke.py",
                os.path.join("tests", "golden", "workload.py"),
                os.path.join("tests", "golden", "dataset.py")):
        assert not _imports(os.path.join(REPO, rel)) \
            & {"jax", "jaxlib", "dgraph_tpu"}, rel


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_judge(smoke):
    ok = (200, {"data": {"q": [{"uid": "0x1"}]}})
    tag = "query_device_sort_page_total"
    assert smoke.judge("q", (tag,), ok, ok, {tag: 1.0}) == []
    # answered correctly, counter flat: the host answered it
    flat = smoke.judge("q058", (tag,), ok, ok, {})
    assert len(flat) == 1 and "q058" in flat[0] and tag in flat[0]
    # an errors member fails even next to good data
    err = (200, {"data": ok[1]["data"], "errors": [{"message": "x"}]})
    assert smoke.judge("q", (), err, ok, {})
    assert smoke.judge("q", (), ok, err, {})
    assert smoke.judge("q", (), (500, {}), ok, {})
    other = (200, {"data": {"q": [{"uid": "0x2"}]}})
    assert "disagree" in smoke.judge("q", (), ok, other, {})[0]
    # key order is not a difference
    a = (200, {"data": {"q": [{"a": 1, "b": 2}]}})
    b = (200, {"data": {"q": [{"b": 2, "a": 1}]}})
    assert smoke.judge("q", (), a, b, {}) == []


def test_smoke_runtime_and_kernel_checks(smoke):
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    good = {"runtime": {"device": dev, "native": True}}
    assert smoke.check_runtime("a", good) == []
    no_native = {"runtime": {"device": dev, "native": False,
                             "nativeUnavailableReason": "make: g++"}}
    assert "make: g++" in smoke.check_runtime("a", no_native)[0]
    on_cpu = {"runtime": {"device": dict(dev, platform="cpu"),
                          "native": True}}
    assert smoke.check_runtime("a", on_cpu)
    assert smoke.check_runtime("a", {})
    assert smoke.check_kernels({
        "a": {"ok": True}, "b": {"ok": False, "error": "Mosaic: no"}}) \
        == ["kernel b: Mosaic: no"]


def test_smoke_asserts_every_reachable_family(smoke):
    """Each device family the served path can reach at the default
    scale is some query's must-move tag; the rest are named."""
    tagged = {c for _n, _q, tags in smoke.build_queries(1) for c in tags}
    assert tagged == {
        smoke.FWD, smoke.REV, smoke.FUSED,
        "query_device_sort_page_total", "query_device_count_page_total",
        "query_device_multisort_total", "query_device_setops_total"}
    assert not tagged & set(smoke.NOT_ASSERTED)


def test_smoke_reports_what_it_cut(smoke):
    edges = {"starring": 1_049_844, "genre": 583_692}
    cut = smoke.reduced({"scale": 250, "rdf": 6_683_308,
                         "traversed_edges": edges})
    assert cut["scale"] == {"run": 250, "full": 800, "rdf": 6_683_308}
    assert cut["traversed_predicates_under_1M_edges"] == {
        "genre": 583_692}
    assert smoke.reduced({"scale": 800, "rdf": 21_400_000,
                          "traversed_edges": {"genre": 1_900_000}}) == {}


def test_smoke_last_line_is_the_verdict_alone(smoke, monkeypatch, capsys):
    """A passed run ends on {"ok", "device": {"platform", "kind",
    "count"}} and nothing else; the observations go on the line
    before it."""
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(smoke, "smoke", lambda args, workdir: {
        "device": dict(dev), "data": {"scale": 250}, "kernels": {}})
    assert smoke.main([]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": dev}
    seen = json.loads(lines[-2])
    assert seen["claim"] is None
    assert seen["observations"]["data"] == {"scale": 250}


def test_smoke_metrics_parser(smoke):
    text = ('# TYPE query_device_expand_total counter\n'
            'query_device_expand_total{dir="fwd"} 3\n'
            'device_cache_bytes 1.5e+06\n')
    assert smoke.parse_metrics(text) == {
        'query_device_expand_total{dir="fwd"}': 3.0,
        "device_cache_bytes": 1.5e6}


def test_smoke_fails_without_a_chip(tmp_path):
    """No accelerator: non-zero exit and NO result on stdout — and in
    seconds, because a data-less alpha reaches for the chip first."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--scale", "1", "--budget", "120"],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "alpha-kernels exited" in r.stderr


def test_smoke_alone_is_not_the_program(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else."""
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"],
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=60)
    assert r.returncode != 0 and r.stdout.strip() == ""
