"""Test config: an 8-device virtual CPU mesh, set up before jax imports.

Mirrors the reference's approach of testing multi-node topologies on one
machine (docker-compose, SURVEY §4.5) — here the "cluster" is 8 virtual XLA
CPU devices, so sharding/collective code paths compile and run in CI
without TPU hardware. Tests are CPU-only by design: the tier-1 command
sets JAX_PLATFORMS=cpu, and the line below makes a bare `pytest` do the
same. The chip is exercised by chip_smoke.py, not from here.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# XLA compiles are slow next to the tests themselves; the persistent
# compile cache (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)
# makes repeat runs cheap.
from dgraph_tpu.utils.backend import configure_compile_cache  # noqa: E402

configure_compile_cache()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from the "
        "default `-m 'not slow'` tier-1 run")
    config.addinivalue_line(
        "markers", "failpoint: arms utils/failpoint injection points "
        "(must clear them; the leak guard below enforces it)")
    config.addinivalue_line(
        "markers", "lockcheck: arms the utils/lockcheck runtime "
        "lock-order witness for the test (module-wide via "
        "pytestmark in the tier-1 concurrency files); a witnessed "
        "inversion fails the test with both stacks")
    config.addinivalue_line(
        "markers", "racecheck: arms the utils/racecheck attribute-"
        "level data-race witness (registered concurrency-plane "
        "classes get sampled access instrumentation; kwargs "
        "strict=/sample= pass through); a witnessed race fails the "
        "test with both access stacks")


@pytest.fixture(autouse=True)
def _lockcheck_witness(request):
    """Opt-in runtime lock-order witness (dglint DG12's dynamic
    complement): tests/modules marked `lockcheck` run with every
    project-created lock instrumented; any inversion witnessed during
    the test fails it with the first-seen and current stacks."""
    marker = request.node.get_closest_marker("lockcheck")
    if marker is None:
        yield
        return
    from dgraph_tpu.utils import lockcheck

    lockcheck.enable(strict=bool(marker.kwargs.get("strict", False)))
    try:
        yield
    finally:
        found = lockcheck.disable()
    if found:
        pytest.fail(
            "lock-order inversion(s) witnessed by utils/lockcheck:\n"
            + "\n".join(str(v) for v in found))


@pytest.fixture(autouse=True)
def _racecheck_witness(request):
    """Opt-in attribute-level data-race witness (dglint DG13's dynamic
    complement): tests/modules marked `racecheck` run with the
    registered concurrency-plane classes' attribute accesses sampled;
    any write/write or read/write pair from different threads with no
    common lock fails the test with both access stacks."""
    marker = request.node.get_closest_marker("racecheck")
    if marker is None:
        yield
        return
    from dgraph_tpu.utils import racecheck

    racecheck.enable(
        strict=bool(marker.kwargs.get("strict", False)),
        sample=int(marker.kwargs.get("sample", 1)))
    try:
        yield
    finally:
        found = racecheck.disable()
    if found:
        pytest.fail(
            "data race(s) witnessed by utils/racecheck:\n"
            + "\n".join(str(v) for v in found))


@pytest.fixture(autouse=True)
def _metrics_and_span_leak_guard():
    """Counters, the span ring and the request log are process-global:
    a test that asserts on them while inheriting another test's
    increments is order-dependent and un-bisectable. Reset them AFTER
    every test (resetting before would hide in-test accumulation the
    test itself arranged), and restore tracing to its enabled
    default in case a test toggled it."""
    yield
    from dgraph_tpu.utils import (
        coststore, metrics, reqlog, tracing, watchdog,
    )

    # the alerting plane first: a leaked watchdog thread holds a
    # reqlog observer and keeps mutating counters while the resets
    # below run (stop() also forgets the shared AlertManager, so
    # firing/hysteresis state never crosses tests)
    watchdog.stop()
    metrics.reset()
    tracing.clear()
    tracing.set_enabled(True)
    reqlog.reset()
    # the observed-cost store aggregates from the always-on span
    # observer: reset it with the rest of the observability plane so
    # its Prometheus renderer output stays test-local too
    coststore.reset()
    coststore.set_enabled(True)


@pytest.fixture(autouse=True)
def _failpoint_leak_guard():
    """A failpoint armed in one test and leaked into the next makes
    failures order-dependent and un-bisectable: fail the leaking test
    itself, then clear so the rest of the run stays healthy."""
    from dgraph_tpu.utils import failpoint

    yield
    leaked = failpoint.armed()
    if leaked:
        failpoint.clear()
        pytest.fail(
            f"test leaked armed failpoints: {leaked} — arm() must be "
            "paired with disarm()/clear() (use the `failpoint` marker "
            "and a try/finally)")


@pytest.fixture(autouse=True)
def _netfault_leak_guard():
    """Same contract for the network fault plane (utils/netfault): a
    leaked drop rule would silently partition every later test's
    cluster traffic — fail the leaking test, then heal."""
    from dgraph_tpu.utils import netfault

    yield
    leaked = netfault.rules()
    if leaked:
        netfault.clear()
        pytest.fail(
            f"test leaked armed network-fault rules: {leaked} — "
            "pair add_rule()/set_rules() with clear() in a "
            "try/finally")
