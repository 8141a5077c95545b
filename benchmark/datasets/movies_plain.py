"""The plain reference of the movie graph: answers worked out from the
generator's own columns, in plain Python, importing nothing of the
program and taking nothing the program has made.

`ANSWERS[template name](dataset, scale, facts, query)` gives the value
of the reply's `data` member as Python objects (`dataset` is the
dataset module, for its uid layout; `facts` its columns). A template
without an entry has no plain answer here and is held to the postings
tier alone (run.py). Those are the ones that order by `name`, at the
root or in a child: the program orders strings by their first 8 bytes
and then by uid, which is not the source's order, so a plain answer
would never match (PERF.md, open questions); they wait for that to be
settled.

Semantics taken from the source's documentation: an order's ties are
broken by uid ascending; `after: U` with an order starts behind U in
the ordered stream; `offset: K` drops K, `first: N` keeps N;
`between` includes both ends; `anyofterms` matches a name that holds
any of the words, whatever their case; a datetime prints as RFC 3339.
Film i is uid BASES["film"] * scale + i.
"""

from __future__ import annotations

import re


def _int_arg(query: str, name: str) -> int:
    return int(re.search(rf"\b{name}:\s*(0x[0-9a-fA-F]+|\d+)", query).group(1), 0)


def _films(dataset, scale) -> range:
    return range(dataset.PER_SCALE["film"] * scale)


def sort_page_after(dataset, scale, facts, query):
    """has(rating), orderasc: rating, first: N, after: U {uid rating}."""
    rating = facts["rating"]
    order = sorted(range(len(rating)), key=lambda i: (rating[i], i))
    base = dataset.BASES["film"] * scale
    cursor = _int_arg(query, "after") - base
    start = order.index(cursor) + 1
    first = _int_arg(query, "first")
    return {"q": [{"uid": hex(base + i), "rating": rating[i]}
                  for i in order[start:start + first]]}


def _rfc3339(yyyymmdd: int) -> str:
    return (f"{yyyymmdd // 10000:04d}-{yyyymmdd // 100 % 100:02d}-"
            f"{yyyymmdd % 100:02d}T00:00:00Z")


def date_window(dataset, scale, facts, query):
    """between(initial_release_date, "a", "b"), orderasc: the date,
    first: N {name initial_release_date}."""
    lo, hi = (int(d.replace("-", "")) for d in
              re.findall(r'"(\d{4}-\d{2}-\d{2})"', query))
    date, name = facts["date"], facts["name"]
    films = _films(dataset, scale)
    hits = sorted((i for i in films if lo <= date[i] <= hi),
                  key=lambda i: (date[i], i))
    return {"q": [{"name": name[i],
                   "initial_release_date": _rfc3339(date[i])}
                  for i in hits[:_int_arg(query, "first")]]}


def runtime_window(dataset, scale, facts, query):
    """between(runtime, a, b), orderasc: runtime, offset: K, first: N
    {name runtime rating}."""
    lo, hi = map(int, re.search(r"between\(runtime,\s*(\d+),\s*(\d+)\)",
                                query).groups())
    runtime = facts["runtime"]
    films = _films(dataset, scale)
    hits = sorted((i for i in films if lo <= runtime[i] <= hi),
                  key=lambda i: (runtime[i], i))
    k = _int_arg(query, "offset")
    return {"q": [{"name": facts["name"][i], "runtime": runtime[i],
                   "rating": facts["rating"][i]}
                  for i in hits[k:k + _int_arg(query, "first")]]}


def terms_count_and_top(dataset, scale, facts, query):
    """hits = anyofterms(name, "w1 w2") that has(rating): their count,
    and the first N by rating descending {name rating}. Only films
    have a rating."""
    words = set(re.search(r'anyofterms\(name,\s*"([^"]*)"\)',
                          query).group(1).lower().split())
    name, rating = facts["name"], facts["rating"]
    films = _films(dataset, scale)
    hits = [i for i in films if words & set(name[i].lower().split())]
    top = sorted(hits, key=lambda i: (-rating[i], i))
    return {"total": [{"count": len(hits)}],
            "top": [{"name": name[i], "rating": rating[i]}
                    for i in top[:_int_arg(query, "first")]]}


ANSWERS = {
    "q034_date_index": date_window,
    "q044_count_uid_var": terms_count_and_top,
    "q045_between_runtime_offset": runtime_window,
    "q058_after_with_sort": sort_page_after,
}
