"""The one general traffic generator.

A traffic mix is a data file, benchmark/traffic/<name>.json:

  {"loop": "closed", "clients": 8, "bindings": 4,
   "uid_literals": {"zipf": 0.99},
   "templates": [{"name": "q058", "file": "queries/q058.gql",
                  "params": {"first": {"int": [3, 6]}}}, ...]}

A template is query text. Two things in it are rebound per seed:
  * every scale-1 uid literal (0x20007 = film 7 of the golden suite)
    becomes an entity of the same class of the scaled graph, its index
    drawn from a scrambled Zipf over the class's range;
  * every `$name` is replaced by a value drawn as `params` says:
      {"int": [lo, hi]}     uniform whole number
      {"choice": [...]}     one of the values
Each template gets up to `bindings` distinct bindings; the pool is
their list in template order. The request sequence is rounds, each a
seeded shuffle of the templates, so every template is sent equally
often; round r sends binding (r mod its bindings) of each template.
The same seed gives the same pool and the same sequence, byte for
byte. Nothing here imports jax or the program.
"""

from __future__ import annotations

import json
import os
import random
import re

import numpy as np

_UID_RE = re.compile(r"0x[0-9a-fA-F]+")
_PARAM_RE = re.compile(r"\$([A-Za-z_][A-Za-z0-9_]*)")
# Knuth's multiplicative hash: scatters the Zipf's hot ranks over the
# class's range, as YCSB's scrambled-zipfian does
_SCRAMBLE = 2654435761


def _mix(seed: int, *parts) -> int:
    """One integer from the seed and a few labels; stable across
    processes (unlike hash())."""
    h = seed & 0xFFFFFFFFFFFF
    for p in parts:
        for ch in str(p).encode():
            h = (h * 1000003 ^ ch) & 0xFFFFFFFFFFFFFFFF
    return h


class Zipf:
    """Ranks 1..n with P(k) ~ 1/k^s, scrambled over [0, n)."""

    def __init__(self, s: float):
        self.s = s
        self._cdf: dict[int, np.ndarray] = {}

    def draw(self, rng: random.Random, n: int) -> int:
        if n <= 1:
            return 0
        cdf = self._cdf.get(n)
        if cdf is None:
            w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** self.s
            cdf = np.cumsum(w)
            cdf /= cdf[-1]
            self._cdf[n] = cdf
        rank = int(np.searchsorted(cdf, rng.random(), side="right"))
        return (min(rank, n - 1) * _SCRAMBLE) % n


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    for key in ("loop", "clients", "bindings", "templates"):
        if key not in mix:
            raise ValueError(f"{path}: traffic mix lacks {key!r}")
    if mix["loop"] != "closed":
        raise ValueError(f"{path}: only a closed loop is implemented "
                         f"(got {mix['loop']!r})")
    base = os.path.dirname(os.path.abspath(path))
    for t in mix["templates"]:
        with open(os.path.join(base, t["file"])) as f:
            t["text"] = f.read()
    return mix


def _draw_param(spec: dict, rng: random.Random) -> str:
    if "int" in spec:
        lo, hi = spec["int"]
        return str(rng.randint(lo, hi))
    if "choice" in spec:
        return str(rng.choice(spec["choice"]))
    raise ValueError(f"unknown parameter kind {spec!r}")


def bind(template: dict, rng: random.Random, zipf: Zipf, dataset,
         scale: int, facts: dict) -> tuple[str, dict]:
    """One binding of a template's text, and the parameters drawn."""
    params = template.get("params", {})
    drawn = {name: _draw_param(params[name], rng) for name in sorted(params)}

    def sub_uid(m):
        hit = dataset.class_of_literal(int(m.group(0), 16))
        if hit is None:
            return m.group(0)
        first, n = dataset.class_range(hit[0], scale, facts)
        return hex(first + zipf.draw(rng, n))

    def sub_param(m):
        if m.group(1) not in drawn:
            raise ValueError(f"template {template['name']}: ${m.group(1)} "
                             "has no entry under params")
        return drawn[m.group(1)]

    text = _UID_RE.sub(sub_uid, template["text"])
    # a template without `params` keeps its `$` (a regexp's anchor)
    return (_PARAM_RE.sub(sub_param, text) if params else text), drawn


def build_pool(mix: dict, dataset, scale: int, facts: dict,
               seed: int) -> list[dict]:
    """[{"template": index, "name": ..., "binding": k, "query": text,
    "params": {drawn}}]
    in template order; duplicates of a template's binding dropped."""
    zipf = Zipf(float(mix.get("uid_literals", {}).get("zipf", 0.99)))
    pool = []
    for ti, t in enumerate(mix["templates"]):
        rng = random.Random(_mix(seed, "bind", t["name"]))
        seen: list[str] = []
        drawn: list[dict] = []
        # a template with nothing to rebind yields one binding; a few
        # extra draws make up for duplicates among small ranges
        for _ in range(3 * mix["bindings"]):
            q, params = bind(t, rng, zipf, dataset, scale, facts)
            if q not in seen:
                seen.append(q)
                drawn.append(params)
            if len(seen) == mix["bindings"]:
                break
        for k, q in enumerate(seen):
            pool.append({"template": ti, "name": t["name"],
                         "binding": k, "query": q, "params": drawn[k]})
    return pool


class Sequence:
    """The request order: request i -> index into the pool."""

    def __init__(self, pool: list[dict], n_templates: int, seed: int):
        self.seed = seed
        self.n = n_templates
        self.by_template: list[list[int]] = [[] for _ in range(n_templates)]
        for i, e in enumerate(pool):
            self.by_template[e["template"]].append(i)
        self._round = -1
        self._order: list[int] = []

    def at(self, i: int) -> int:
        r, pos = divmod(i, self.n)
        if r != self._round:
            order = list(range(self.n))
            random.Random(_mix(self.seed, "round", r)).shuffle(order)
            self._round, self._order = r, order
        bindings = self.by_template[self._order[pos]]
        return bindings[r % len(bindings)]
