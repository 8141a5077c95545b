"""The roofline yardstick: bytes worked out by hand from the graph's
sizes, and the reader's arithmetic on a reduced trace."""

import io
import os

import pytest

from conftest import BENCH, load


def load_path(path):
    return load(os.path.relpath(path, BENCH))


@pytest.fixture(scope="module")
def world(movies):
    scale = 2
    facts = movies.write_rdf(io.StringIO(), scale, 5)
    pool = [{"name": "q010_count_filter", "params": {"first": "4", "n": "2"}},
            {"name": "q010_count_filter", "params": {"first": "8", "n": "3"}},
            {"name": "q058_after_with_sort", "params": {"first": "5"}},
            {"name": "q008_multi_sort", "params": {"word": "storm"}}]
    return scale, facts, pool


def test_sizes_and_bytes(movies, world):
    scale, facts, pool = world
    costs = load("kernel_costs.py")
    s = costs.graph_sizes(movies, scale, facts, pool)
    assert s["films"] == 2400
    assert s["named"] == 2 * 3720 + 54 and s["perfs"] == facts["perfs"]
    assert s["params"]["q010_count_filter"] == {"first": 6.0, "n": 2.5}
    assert "q008_multi_sort" not in s["params"]  # a word is no size
    count_page = costs.find("jit_count_filter_sort_page", BENCH, load_path)
    sort_page = costs.find("jit_multisort_page", BENCH, load_path)
    assert count_page.least_bytes(s) == 4 * (3 * 2400 + 6)
    assert sort_page.least_bytes(s) == 4 * (2 * 2400 + 5)
    assert costs.find("jit_run", BENCH, load_path) is None
    assert costs.find("../kernel_costs", BENCH, load_path) is None


def test_roofline_share_of_the_top_costed_program(movies, world):
    scale, facts, pool = world
    reader = load("metrics/top_program_roofline.py")
    ctx = {"trace": {"programs": [["jit_run", 0.5, 9],
                                  ["jit_count_filter_sort_page", 0.001, 2],
                                  ["jit_multisort_page", 0.0005, 4]]},
           "peaks": {"hbm_bytes_per_s": 819e9}, "pool": pool,
           "scale": scale, "facts": facts, "dataset": movies,
           "bench_dir": BENCH, "notes": [], "load_module": load_path}
    # jit_run has no cost function: the next program by time is taken
    want = 100.0 * (4 * (3 * 2400 + 6) * 2 / 819e9) / 0.001
    assert reader.read(ctx) == pytest.approx(want)
    assert 0 < want < 100 and "jit_count_filter_sort_page" in ctx["notes"][0]
    # a mix without the program's template reports nothing for it
    ctx["pool"] = [e for e in pool if e["name"] == "q008_multi_sort"]
    assert reader.read(ctx) is None
    ctx["trace"] = None
    assert reader.read(ctx) is None
