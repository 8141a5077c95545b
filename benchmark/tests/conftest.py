"""Helpers of the benchmark's own tests (run them with
`JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`; tier-1's
`pytest tests/` does not collect this directory)."""

import importlib.util
import os
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


@pytest.fixture(scope="session")
def traffic():
    return load("traffic.py")


@pytest.fixture(scope="session")
def movies():
    return load("datasets/movies.py")
