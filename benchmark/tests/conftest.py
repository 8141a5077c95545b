"""Helpers of the benchmark's own tests (run them with
`JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`; tier-1's
`pytest tests/` does not collect this directory)."""

import importlib.util
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load(relpath: str):
    path = os.path.join(BENCH, relpath)
    name = "benchtest_" + relpath.replace("/", "_").removesuffix(".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def copy_checkout(root: str) -> str:
    """A checkout of its own under `root`: the benchmark's files
    copied (a test may then add to them or break them), the program
    linked in; -> its benchmark directory."""
    bdir = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, bdir, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__"))
    for name in ("dgraph_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    return bdir


def alter_answers(launcher: str, member: str) -> None:
    """Break the timed path underneath the harness: rewrite the chip
    child's launcher so that the engine's serialized reply gets a
    digit put before the first value of `member`, where the answer is
    produced."""
    with open(launcher) as f:
        src = f.read()
    marker = "    from dgraph_tpu.cli import main as cli_main\n"
    assert marker in src
    patch = (
        "    import dgraph_tpu.engine.db as _db\n"
        "    _q = _db.GraphDB.query_json\n"
        "    def _altered(self, q, *a, **kw):\n"
        "        return _q(self, q, *a, **kw).replace(\n"
        f"            '\"{member}\":', '\"{member}\":1', 1)\n"
        "    _db.GraphDB.query_json = _altered\n")
    with open(launcher, "w") as f:
        f.write(src.replace(marker, patch + marker))


@pytest.fixture(scope="session")
def traffic():
    return load("traffic.py")


@pytest.fixture(scope="session")
def movies():
    return load("datasets/movies.py")
