"""trace_reduce.py on a small recorded trace whose numbers were worked
out by hand (small_trace.pbtxt: one chip, a 10 ms window)."""

import os

import pytest

from conftest import BENCH, load


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    with open(os.path.join(BENCH, "tests", "small_trace.pbtxt")) as f:
        pd = ProfileData.from_text_proto(f.read())
    return load("trace_reduce.py").reduce_profile(pd)


def test_busy_and_window(reduced):
    # ops cover [0,3] (two overlapping), [5,6], [8,9] ms of [0,10] ms
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(0.010)
    assert reduced["busy_s"] == pytest.approx(0.005)


def test_op_and_program_times(reduced):
    assert dict(map(tuple, reduced["device_ops"])) == pytest.approx(
        {"jit_multisort/sort.1": 0.003, "jit_multisort/fusion.2": 0.0015,
         "jit_expand/gather.3": 0.001})
    # the fingerprint in brackets is dropped from a program's name
    assert reduced["programs"][0] == ["jit_multisort",
                                      pytest.approx(0.004), 2]
    assert reduced["programs"][1] == ["jit_expand",
                                      pytest.approx(0.001), 1]


def test_idle_gaps_longest_first_with_host_label(reduced):
    gaps = reduced["idle_gaps"]
    assert [round(g[1], 6) for g in gaps] == [0.002, 0.002, 0.001]
    # [3,5] ms is covered by the inner `encode` span, [6,8] only by
    # the outer `request`
    assert sorted(g[0] for g in gaps[:2]) == ["encode", "request"]


def test_no_device_plane_gives_no_busy_time():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "t" '
        'events { metadata_id: 1 offset_ps: 0 duration_ps: 5000 } } '
        'event_metadata { key: 1 value { id: 1 name: "x" } } }')
    out = load("trace_reduce.py").reduce_profile(pd)
    assert out["busy_s"] is None and out["chips"] == 0
