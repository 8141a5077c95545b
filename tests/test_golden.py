"""Golden-query conformance suite.

The reference's acceptance bar is a set of canned queries JSON-diffed
against committed golden outputs over the 21million movie graph
(systest/21million/test-21million.sh, queries/query-0??). This is the
same suite at ~1/200 scale: a deterministic movie-shaped dataset
(tests/golden/dataset.py), 35 queries spanning the whole query surface
(tests/golden/queries/*.gql), and committed goldens
(tests/golden/expected/*.json). ANY drift in query output — ordering,
facet shape, pagination, stemming — fails here.

Float leaves compare with a relative tolerance (the reference's own
acceptance diff normalizes %f output): an aggregation pipeline is free
to reassociate a float sum (28.87 vs 28.870000000000005) without that
counting as drift, while ints, strings, key sets, ordering and shape
stay byte-exact.

To intentionally change an output: `python -m tests.golden.regen` and
review the diff.
"""

import json
import math

import pytest

from tests.golden import runner


def _json_close(a, b) -> bool:
    """Structural equality with float-tolerant leaves. Everything else
    — type, shape, ordering, key sets — must match exactly; ints and
    floats never cross-match (a tier converting 5 to 5.0 is a bug)."""
    if isinstance(a, float) and isinstance(b, float):
        if math.isnan(a) or math.isnan(b):
            return math.isnan(a) and math.isnan(b)
        return math.isclose(a, b, rel_tol=1e-9, abs_tol=1e-12)
    if type(a) is not type(b):
        return False
    if isinstance(a, dict):
        return a.keys() == b.keys() \
            and all(_json_close(v, b[k]) for k, v in a.items())
    if isinstance(a, list):
        return len(a) == len(b) \
            and all(_json_close(x, y) for x, y in zip(a, b))
    return a == b


@pytest.mark.parametrize("name", runner.query_names())
def test_golden(name):
    got = runner.run_query(name)
    want = runner.load_expected(name)
    assert _json_close(got, want), (
        f"{name} drifted from its golden output.\n"
        f"got:  {json.dumps(got)[:2000]}\n"
        f"want: {json.dumps(want)[:2000]}\n"
        "If the change is intended: python -m tests.golden.regen "
        f"{name.split('_')[0]}"
    )


def test_json_close_is_strict():
    # the tolerance opens ONLY the float-vs-float leaf comparison
    assert _json_close({"x": 28.87}, {"x": 28.870000000000005})
    assert not _json_close({"x": 5}, {"x": 5.0})
    assert not _json_close([1, 2], [2, 1])
    assert not _json_close({"x": 1}, {"x": 1, "y": 2})
    assert not _json_close({"x": "a"}, {"x": "a "})
    assert not _json_close({"x": 28.87}, {"x": 28.88})


def test_every_query_has_a_golden():
    names = runner.query_names()
    assert len(names) >= 35
    for n in names:
        runner.load_expected(n)  # raises if missing


def test_device_programs_carry_names_of_their_own():
    """After the suite's device-forced pass (device_min_edges=1 in
    the runner): every executable the served path compiled says what
    it is in a device profile, and the two programs the benchmark has
    costed (benchmark/costs/jit_<name>.py) keep their names."""
    import os

    from dgraph_tpu.ops import graph
    from dgraph_tpu.query import plan

    for name in runner.query_names():
        if name.startswith(("q006", "q010", "q049", "q058")):
            runner.run_query(name)  # alone too, the registry is filled
    anonymous = {"run", "<lambda>", "fn", "f"}
    with plan._JIT_LOCK:
        staged = {key[0]: fn.__name__ for key, fn in plan._JIT.items()}
    assert staged and not anonymous & set(staged.values()), staged
    assert any(n.startswith("fused_page_") for n in staged.values())
    expanders = [
        fn.__name__ for tab in runner.get_db().tablets.values()
        for attr in ("_device_adj", "_device_radj")
        for fn in getattr(getattr(tab, attr, None),
                          "_expander_cache", {}).values()]
    assert expanders and set(expanders) == {"expand_frontier"}
    costs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "costs")
    for fn in (graph.count_filter_sort_page, graph.multisort_page):
        assert os.path.isfile(os.path.join(
            costs, f"jit_{fn.__name__}.py")), fn.__name__
    assert graph.count_filter_sort_page.__name__ \
        == "count_filter_sort_page"
    assert graph.multisort_page.__name__ == "multisort_page"
