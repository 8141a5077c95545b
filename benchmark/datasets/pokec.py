"""A seeded social graph in the shape of SNAP's soc-Pokec.

The graph of the ArangoDB NoSQL performance benchmark's "shortest
path" test is the Pokec social network: 1,632,803 profiles and
30,622,564 DIRECTED friendship edges (mean out-degree 18.75), the
largest out-degree 8,763 and in-degree 13,733, about 54% of the edges
reciprocated. There is no network here and the SNAP file is not in
the repository, so the graph is DRAWN from the seed to those figures
(every choice below is listed under `assumed` in
configs/pokec-shortest.json):

  * `scale` is the PERCENT of the source's profiles: 100 is the
    source, 12 about 196,000 profiles. Nothing else follows the
    scale: the mean out-degree, the share of reciprocated edges and
    the degree law are the source's at every scale.
  * Degrees: every profile has an out-weight and an in-weight from
    two log-normals (sigma 1.44 and 1.56: each the log-normal whose
    mean is 18.75 and whose 1 - 1/V quantile at the source's V is the
    source's largest degree), their logarithms correlated 0.5. The
    weights are the law's own quantiles, one a profile, the same for
    every seed of a scale (the source has ONE degree sequence); the
    seed deals them to the profiles. An edge's source is drawn in
    proportion to the out-weights and its destination to the
    in-weights (a Chung-Lu draw), so a profile's degrees are Poisson
    about its weights.
  * Distances: friends are mostly NEAR. A profile's label is its
    place on a ring (a region, a town: the weights are drawn
    independently of it), and the drawn ends of the edges are matched
    by place, not at random: every end gets a key, its profile's
    place moved by up to the profile's REACH either way (200 places
    times the profile's weight over the mean weight, at least 200: a
    sociable profile is known further away), or, one end in forty, a
    place anywhere on the ring; the out-ends sorted by key are matched
    with the in-ends sorted by key. Every profile keeps the ends it
    drew, so the degree law is untouched. The two figures (LOCAL,
    REACH) are fitted at the source's scale to the source's 90%
    effective diameter of 5.2-5.3 (a drawn graph of 1,632,803 profiles
    reads 5.24, mean 4.81 hops, from 160 sources; with the ends
    matched at random, 4.77 and 4.27); a graph of fewer profiles is
    shallower, as any is.
  * Reciprocity: 26.4% of the edge budget is drawn as PAIRS, both
    directions loaded; the rest one way. A one-way edge between near
    profiles often has its reverse drawn too (1.2% of the edges at
    every scale), which makes up the source's 54%. (A pair gives both
    its ends an in- and an out-edge, so that share of a profile's
    expected out-degree follows its in-weight.)
  * Duplicates and self-loops are dropped, as a loader's set
    semantics drop them, and the shortfall drawn again, so that the
    mean out-degree stays the source's.

One predicate, `friend`, directed. Profiles are numbered in three
runs, each in the order of their drawn labels: those with out-edges
only, those with both, those with in-edges only; a profile no edge
touches is no vertex. Vertex i is uid FIRST_UID + i, so the usable
sources of a path (class "from": a profile with an out-edge) and its
usable targets (class "to": a profile with an in-edge) are each ONE
contiguous range, which class_range() gives.

A dataset module gives: SCHEMA, CLASSES, class_of_literal(),
class_range(), write_rdf(). Pure numpy and stdlib: the harness's
parent imports it and must never import jax or the program (the one
question it has for the program, `require_defined_path()`, is asked
in a child).
"""

from __future__ import annotations

import os
import subprocess
import sys
from statistics import NormalDist

import numpy as np

SCHEMA = """
friend: [uid] .
"""

# the source's figures, never cut
PROFILES = 1_632_803
MEAN_OUT_DEGREE = 18.75
RECIPROCATED = 0.54
# the share of the edges drawn as pairs; one-way edges whose reverse is
# drawn too make up the rest of RECIPROCATED (0.012 at every scale)
PAIRED = 0.528
# fitted at the source's scale to its 90% effective diameter: the
# share of the edges' ends keyed near their profile, and how near, in
# profiles a mean weight
LOCAL, REACH = 0.975, 200
SIGMA_OUT, SIGMA_IN = 1.44, 1.56
WEIGHT_CORRELATION = 0.5
FIRST_UID = 1

CLASSES = ("from", "to")
# a uid literal in a query template is <class base> + i with
# i < 0x10000 (the harness's convention: datasets/movies.py) and
# stands for "an entity of that class"
_BASES = {"from": 0x10000, "to": 0x20000}

# what a control run may serve in place of the sound graph: one edge
# in a thousand left out, which takes the defined path from about one
# pair in two hundred (a path of four or five hops that held such an
# edge): a few dozen of a pool's 4,096 answers, the others untouched
VARIANTS = ("drop-edges",)
_DROP_ONE_IN = 1000

# the checkout this file lies in: benchmark/datasets/pokec.py
PROGRAM_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# A social graph has many shortest paths a pair, and `correct`
# compares bytes: a program whose tiers break ties each in its own
# way (a heap's pop order on the host, the smallest uid on the device)
# is not correct on this cell by construction, and its postings tier
# walks every edge in the interpreter: seconds a query at the cell's
# scale, so the pool's reference would not be answered
# before the harness gives the run up at 1,150 s. A run on such a
# program ends here, in seconds (as datasets/graph500.py's does on a
# program without the frontier search): asked of the program beside
# this file is whether its storage layer has the one DEFINED path
# (docs/deployment.md, "shortest").
_PROBE = "from dgraph_tpu.storage.tablet import least_path"

_CACHE: dict = {}


def class_of_literal(u: int) -> tuple[str, int] | None:
    """(class, index) of a template's uid literal, None if it is
    none."""
    for kind, base in _BASES.items():
        if base <= u < base + 0x10000:
            return kind, u - base
    return None


def class_range(kind: str, scale: int, facts: dict) -> tuple[int, int]:
    """(first uid, entity count) of a class: "from" the profiles with
    an out-edge, "to" those with an in-edge."""
    if kind not in _BASES:
        raise ValueError(f"unknown class {kind!r}")
    return int(facts[kind + "_first"]), int(facts[kind + "_count"])


def require_defined_path() -> None:
    """Raise unless the program beside this file answers `shortest`
    with the one defined path from every tier. Asked once a process,
    in a CPU child: under a second."""
    if _CACHE.get("program") == PROGRAM_ROOT:
        return
    probe = subprocess.run(
        [sys.executable, "-c", _PROBE], cwd=PROGRAM_ROOT,
        env=os.environ | {"JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    if probe.returncode != 0:
        raise RuntimeError(
            "this program cannot run the pokec configuration: its "
            "tiers break ties between equal-length paths each in its "
            "own way (no storage/tablet.least_path), so its replies "
            "cannot be held to one reference byte for byte; "
            + (probe.stderr.strip().splitlines() or ["no message"])[-1])
    _CACHE["program"] = PROGRAM_ROOT


def _ends(rng: np.random.Generator, weight: np.ndarray, n: int
          ) -> np.ndarray:
    """`n` edge ends, each a label drawn in proportion to its weight,
    in the order of their keys on the ring: an end's key is its
    profile's place moved by up to the profile's reach either way, or
    (one in forty) any place."""
    profiles = len(weight)
    cdf = np.cumsum(weight)
    who = np.minimum(np.searchsorted(cdf, cdf[-1] * rng.random(n),
                                     side="right"), profiles - 1)
    reach = np.clip(REACH * weight[who] / weight.mean(), REACH,
                    profiles / 2)
    key = who + reach * rng.uniform(-1.0, 1.0, n)
    far = rng.random(n) >= LOCAL
    key[far] = profiles * rng.random(int(far.sum()))
    return who[np.argsort(key % profiles)].astype(np.int64)


def drawn_edges(profiles: int, rng: np.random.Generator) -> np.ndarray:
    """The distinct directed edges over `profiles` labels, packed
    src << 32 | dst and sorted: MEAN_OUT_DEGREE x profiles of them
    (within a thousandth), PAIRED of them drawn in pairs."""
    want = round(MEAN_OUT_DEGREE * profiles)
    # the law's own quantiles, one a profile, and ONE pairing of out-
    # with in-weights a scale: every seed's graph has the same weights
    # (drawn one by one, the few largest, and the few profiles large
    # both ways, would differ by half between seeds, and with them how
    # deep the graph is); the seed deals them to the places
    inv = NormalDist().inv_cdf
    z = np.array([inv((i + 0.5) / profiles) for i in range(profiles)])
    b = np.random.default_rng(profiles).permutation(z)
    place = rng.permutation(profiles)
    w_out = np.exp(SIGMA_OUT * z)[place]
    w_in = np.exp(SIGMA_IN * (
        WEIGHT_CORRELATION * z
        + np.sqrt(1.0 - WEIGHT_CORRELATION ** 2) * b))[place]
    del z, b, place
    packed = np.empty(0, np.int64)
    for _ in range(8):
        short = want - len(packed)
        if short <= want // 1000:
            break
        pairs = round(short * PAIRED / 2)
        src = _ends(rng, w_out, short - pairs)
        dst = _ends(rng, w_in, short - pairs)
        both = rng.permutation(short - pairs)[:pairs]
        src, dst = (np.concatenate([src, dst[both]]),
                    np.concatenate([dst, src[both]]))
        keep = src != dst
        packed = np.union1d(packed, (src[keep] << 32) | dst[keep])
    return packed


def graph(scale: int, seed: int, variant: str = ""
          ) -> tuple[np.ndarray, np.ndarray, dict]:
    """(src, dst, ranges): the distinct directed edges as vertex
    indices (uid - FIRST_UID), sorted by (src, dst), and {"vertices",
    "from_first", "from_count", "to_first", "to_count"} (uids). The
    last one made is kept, so the plain reference does not draw it
    again."""
    if variant and variant not in VARIANTS:
        raise ValueError(f"unknown dataset variant {variant!r}")
    key = (scale, seed, variant)
    if _CACHE.get("graph_key") != key:
        rng = np.random.default_rng(seed)
        profiles = round(PROFILES * scale / 100)
        packed = drawn_edges(profiles, rng)
        src, dst = packed >> 32, packed & 0xFFFFFFFF
        has_out = np.zeros(profiles, bool)
        has_in = np.zeros(profiles, bool)
        has_out[src] = True
        has_in[dst] = True
        runs = [np.flatnonzero(has_out & ~has_in),
                np.flatnonzero(has_out & has_in),
                np.flatnonzero(has_in & ~has_out)]
        label = np.full(profiles, -1, np.int64)
        label[np.concatenate(runs)] = np.arange(sum(map(len, runs)))
        packed = np.sort((label[src] << 32) | label[dst])
        src, dst = packed >> 32, packed & 0xFFFFFFFF
        if variant == "drop-edges":
            vrng = np.random.default_rng([seed, 1])
            keep = vrng.random(len(src)) >= 1.0 / _DROP_ONE_IN
            src, dst = src[keep], dst[keep]
        only_out, both, only_in = map(len, runs)
        _CACHE["graph_key"] = key
        _CACHE["graph"] = (src, dst, {
            "vertices": only_out + both + only_in,
            "from_first": FIRST_UID, "from_count": only_out + both,
            "to_first": FIRST_UID + only_out, "to_count": both + only_in})
    return _CACHE["graph"]


# edges turn into text this many at a time
_BLOCK = 1 << 18


def _uid_text(vertices: int) -> np.ndarray:
    """(vertices, w) uint8: every vertex's uid as `<0x...>`, zero-padded
    to one width, so that a block of edges becomes text by fancy
    indexing, with no Python loop over edges."""
    digits = len(f"{FIRST_UID + vertices:x}")
    text = np.array([f"<0x{u:0{digits}x}>".encode() for u in
                     range(FIRST_UID, FIRST_UID + vertices)])
    return text.view(np.uint8).reshape(vertices, -1)


def write_rdf(out, scale: int, seed: int, variant: str = "") -> dict:
    """Write the graph's N-Quads to `out`, one an edge; -> facts.

    facts: {"rdf": lines written, "edges": {predicate: count},
    "vertices", the two classes' ranges ("from_first", "from_count",
    "to_first", "to_count"), "seed", "profiles" (labels drawn over:
    the source's count cut by the scale), "mean_out_degree" (edges a
    profile, as the source reckons its 18.75), "reciprocated" (the
    share of edges whose reverse is an edge too),
    "max_out_degree", "max_in_degree"}: what the traffic generator
    and the size report need, and the seed, from which the plain
    reference (pokec_plain.py) draws the SOUND graph again. `variant`
    "drop-edges" leaves out one edge in a thousand: the degraded graph of
    the control run, never of a measured one. Refuses a program whose
    tiers do not share one defined path (`require_defined_path()`)
    before a byte is written."""
    require_defined_path()
    src, dst, ranges = graph(scale, seed, variant)
    raw = getattr(out, "buffer", None)
    if raw is not None:
        out.flush()
    uid = _uid_text(ranges["vertices"])
    mid = np.frombuffer(b" <friend> ", np.uint8)
    end = np.frombuffer(b" .\n", np.uint8)
    for lo in range(0, len(src), _BLOCK):
        s, d = src[lo:lo + _BLOCK], dst[lo:lo + _BLOCK]
        text = np.concatenate(
            [uid[s], np.broadcast_to(mid, (len(s), len(mid))), uid[d],
             np.broadcast_to(end, (len(s), len(end)))], axis=1).tobytes()
        if raw is not None:
            raw.write(text)
        else:
            out.write(text.decode())
    profiles = round(PROFILES * scale / 100)
    return ranges | degree_figures(src, dst) | {
        "rdf": len(src), "edges": {"friend": len(src)}, "seed": seed,
        "profiles": profiles, "mean_out_degree": len(src) / profiles}


def degree_figures(src: np.ndarray, dst: np.ndarray) -> dict:
    """The figures the source is known by, of an edge list sorted by
    (src, dst)."""
    packed = (src << 32) | dst
    back = np.sort((dst << 32) | src)
    at = np.minimum(np.searchsorted(back, packed), len(back) - 1)
    return {"reciprocated": float(np.mean(back[at] == packed)),
            "max_out_degree": int(np.bincount(src).max()),
            "max_in_degree": int(np.bincount(dst).max())}
