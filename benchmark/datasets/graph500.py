"""The seeded Graph500 Kronecker graph.

The graph of TigerGraph's graph-database benchmark (`graph500-22`) is
the Graph500 generator's: a stochastic Kronecker graph with initiator
A 0.57, B 0.19, C 0.19 (D 0.05) and edge factor 16, its vertex labels
scrambled. `scale` is the Graph500 SCALE, log2 of the number of
labels: 2^scale labels and 16 x 2^scale edges drawn (the source's
graph is scale 22). Every edge is redrawn by the seed. The drawn list
is loaded as the DIRECTED edges of one predicate, `link`, as drawn;
duplicates and self-loops are dropped, as a loader's set semantics
drop them, and a label no edge touches is no vertex.

Vertices that have an out-edge are numbered first (in the order of
their scrambled labels), then the vertices that only have in-edges:
vertex i is uid FIRST_UID + i, so the usable roots of a k-hop query
(the source draws its seeds from vertices that have neighbours) are
the one contiguous range class_range("root") gives.

A dataset module gives: SCHEMA, CLASSES, class_of_literal(),
class_range(), write_rdf(). Pure numpy and stdlib: the harness's
parent imports it and must never import jax or the program (the one
question it has for the program, `require_bound_recurse()`, is asked
in a child).
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

SCHEMA = """
link: [uid] .
"""

# Graph500's initiator and edge factor: the source's, never changed
A, B, C = 0.57, 0.19, 0.19
EDGE_FACTOR = 16
FIRST_UID = 1

CLASSES = ("root",)
# a uid literal in a query template is ROOT_BASE + i with i < 0x10000
# (the harness's convention for a literal of a class: datasets/
# movies.py) and stands for "a root": the traffic generator puts a
# vertex of class_range("root") in its place
ROOT_BASE = 0x10000

# what a control run may serve in place of the sound graph: one edge
# in a thousand left out, which breaks the exact counts
VARIANTS = ("drop-edges",)
_DROP_ONE_IN = 1000

# the checkout this file lies in: benchmark/datasets/graph500.py
PROGRAM_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# A k-hop query of this deployment reaches about the whole graph. A
# program whose @recurse walks its frontier a uid at a time in the
# interpreter takes seconds to minutes for ONE such query, in the
# served tier and in the postings tier that answers for the reference
# alike: it cannot serve the cell inside a run, and write_rdf() says
# so at once. Asked of the program beside this file: whether its
# storage layer has the level-at-a-time traversal.
_PROBE = "from dgraph_tpu.storage.tablet import bfs_levels"

_CACHE: dict = {}


def class_of_literal(u: int) -> tuple[str, int] | None:
    """(class, index) of a template's uid literal, None if it is
    none."""
    if ROOT_BASE <= u < ROOT_BASE + 0x10000:
        return "root", u - ROOT_BASE
    return None


def class_range(kind: str, scale: int, facts: dict) -> tuple[int, int]:
    """(first uid, entity count) of a class: the vertices that have an
    out-edge."""
    if kind != "root":
        raise ValueError(f"unknown class {kind!r}")
    return FIRST_UID, int(facts["roots"])


def require_bound_recurse() -> None:
    """Raise unless the program beside this file traverses a level at
    a time. Asked once a process, in a CPU child: under a second."""
    if _CACHE.get("program") == PROGRAM_ROOT:
        return
    probe = subprocess.run(
        [sys.executable, "-c", _PROBE], cwd=PROGRAM_ROOT,
        env=os.environ | {"JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    if probe.returncode != 0:
        raise RuntimeError(
            "this program cannot run the graph500 configuration inside "
            "a run: its @recurse walks every frontier a uid at a time "
            "on the host (no storage/tablet.bfs_levels), in the served "
            "tier and in the reference's; "
            + (probe.stderr.strip().splitlines() or ["no message"])[-1])
    _CACHE["program"] = PROGRAM_ROOT


def kronecker_edges(scale: int, rng: np.random.Generator
                    ) -> tuple[np.ndarray, np.ndarray]:
    """(src, dst) labels of the EDGE_FACTOR x 2^scale drawn edges, as
    the Graph500 reference generator draws them: one bit of both
    labels a round, then the labels scrambled."""
    m = EDGE_FACTOR << scale
    ab = A + B
    c_norm, a_norm = C / (1.0 - ab), A / ab
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        i_bit = rng.random(m, dtype=np.float32) > ab
        j_bit = rng.random(m, dtype=np.float32) > np.where(
            i_bit, np.float32(c_norm), np.float32(a_norm))
        src |= i_bit.astype(np.int64) << bit
        dst |= j_bit.astype(np.int64) << bit
    scramble = rng.permutation(1 << scale)
    return scramble[src], scramble[dst]


def graph(scale: int, seed: int, variant: str = ""
          ) -> tuple[np.ndarray, np.ndarray, int, int]:
    """(src, dst, roots, vertices): the distinct directed edges as
    vertex indices (uid - FIRST_UID), sorted by (src, dst); vertices
    0..roots-1 are those with an out-edge. The last one made is kept,
    so the plain reference does not draw it again."""
    if variant and variant not in VARIANTS:
        raise ValueError(f"unknown dataset variant {variant!r}")
    key = (scale, seed, variant)
    if _CACHE.get("graph_key") != key:
        rng = np.random.default_rng(seed)
        src, dst = kronecker_edges(scale, rng)
        keep = src != dst
        packed = np.unique((src[keep] << 32) | dst[keep])
        src, dst = packed >> 32, packed & 0xFFFFFFFF
        # number the labels: those with an out-edge first
        label = np.full(1 << scale, -1, np.int64)
        has_out = np.unique(src)
        label[has_out] = np.arange(len(has_out))
        only_in = np.setdiff1d(np.unique(dst), has_out, assume_unique=True)
        label[only_in] = len(has_out) + np.arange(len(only_in))
        packed = np.sort((label[src] << 32) | label[dst])
        src, dst = packed >> 32, packed & 0xFFFFFFFF
        if variant == "drop-edges":
            vrng = np.random.default_rng([seed, 1])
            lost = vrng.choice(len(src), size=max(1, len(src)
                                                  // _DROP_ONE_IN),
                               replace=False)
            keep = np.ones(len(src), bool)
            keep[lost] = False
            src, dst = src[keep], dst[keep]
        _CACHE["graph_key"] = key
        _CACHE["graph"] = (src, dst, len(has_out),
                           len(has_out) + len(only_in))
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
    "roots": vertices with an out-edge, "vertices", "seed",
    "max_out_degree", "max_in_degree"}: what the traffic generator
    and the size report need, and the seed, from which the plain
    reference (graph500_plain.py) draws the SOUND graph again.
    `variant` "drop-edges" leaves out one edge in a thousand: the
    degraded graph of the control run, never of a measured one.
    Refuses a program that cannot serve the cell inside a run
    (`require_bound_recurse()`) before a byte is written."""
    require_bound_recurse()
    src, dst, roots, vertices = graph(scale, seed, variant)
    raw = getattr(out, "buffer", None)
    if raw is not None:
        out.flush()
    uid = _uid_text(vertices)
    mid = np.frombuffer(b" <link> ", np.uint8)
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
    return {"rdf": len(src), "edges": {"link": len(src)},
            "roots": roots, "vertices": vertices, "seed": seed,
            "max_out_degree": int(np.bincount(src).max()),
            "max_in_degree": int(np.bincount(dst).max())}
