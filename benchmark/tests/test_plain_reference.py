"""The plain reference's answers on a graph small enough to work out
by hand (5 films)."""

import pytest

from conftest import load

FACTS = {"rating": [5.0, 3.0, 5.0, 1.0, 3.0],
         "date": [20230301, 20221231, 20230301, 20241231, 20250101],
         "runtime": [171, 179, 170, 180, 171],
         "name": ["Silent Storm Film 0", "Golden River Film 1",
                  "Quiet Silent Film 2", "Blue Star Film 3",
                  "Golden Silent Film 4"]}
SCALE = 1


@pytest.fixture(scope="module")
def plain():
    return load("datasets/movies_plain.py")


class Tiny:
    """A dataset module with five films."""
    BASES = {"film": 0x20000}
    PER_SCALE = {"film": 5}


def film(i):
    return hex(0x20000 + i)


def test_sort_page_starts_behind_the_cursor_in_the_sorted_stream(plain):
    # by (rating, uid): 3, 1, 4, 0, 2; behind film 1 come 4 and 0
    q = "{ q(func: has(rating), orderasc: rating, first: 2, " \
        f"after: {film(1)}) {{ uid rating }} }}"
    assert plain.ANSWERS["q058_after_with_sort"](
        Tiny, SCALE, FACTS, q) == {"q": [
            {"uid": film(4), "rating": 3.0}, {"uid": film(0), "rating": 5.0}]}


def test_date_window_includes_both_ends_and_breaks_ties_by_uid(plain):
    # 2023-2024: films 0 and 2 (same day, uid decides), then 3 on the
    # window's last day; 1 and 4 lie a day outside it
    q = '{ q(func: between(initial_release_date, "2023-01-01", ' \
        '"2024-12-31"), orderasc: initial_release_date, first: 8) ' \
        '{ name initial_release_date } }'
    assert plain.ANSWERS["q034_date_index"](Tiny, SCALE, FACTS, q) == {
        "q": [{"name": FACTS["name"][i], "initial_release_date": d}
              for i, d in ((0, "2023-03-01T00:00:00Z"),
                           (2, "2023-03-01T00:00:00Z"),
                           (3, "2024-12-31T00:00:00Z"))]}


def test_runtime_window_drops_the_offset_then_keeps_first(plain):
    # 170..179 by (runtime, uid): 2, 0, 4, 1; offset 1, first 2: 0, 4
    q = "{ q(func: between(runtime, 170, 179), orderasc: runtime, " \
        "offset: 1, first: 2) { name runtime rating } }"
    assert plain.ANSWERS["q045_between_runtime_offset"](
        Tiny, SCALE, FACTS, q) == {"q": [
            {"name": FACTS["name"][i], "runtime": 171,
             "rating": FACTS["rating"][i]} for i in (0, 4)]}


def test_terms_count_and_top_by_rating_down_uid_up(plain):
    # silent or golden: films 0, 1, 2, 4; by (-rating, uid): 0, 2, 1, 4
    q = '{ hits as var(func: anyofterms(name, "silent golden")) ' \
        '@filter(has(rating)) total(func: uid(hits)) { count(uid) } ' \
        'top(func: uid(hits), orderdesc: rating, first: 3) ' \
        '{ name rating } }'
    assert plain.ANSWERS["q044_count_uid_var"](Tiny, SCALE, FACTS, q) == {
        "total": [{"count": 4}],
        "top": [{"name": FACTS["name"][i], "rating": FACTS["rating"][i]}
                for i in (0, 2, 1)]}


def test_name_ordered_templates_have_no_plain_answer_yet(plain):
    assert set(plain.ANSWERS) == {
        "q034_date_index", "q044_count_uid_var",
        "q045_between_runtime_offset", "q058_after_with_sort"}
