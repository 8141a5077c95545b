"""The seeded SIFT-shaped vector corpus.

Shape of ann-benchmarks' sift-128-euclidean (SIFT1M, TEXMEX): 128
dimensions, components whole numbers 0-255 held as float32, Euclidean
distance. The real file is not fetchable here, so the rows are a
stand-in: a FIXED mixture of 1,024 clusters in SIFT's value range
(the centres never change), every row redrawn by the seed, rounded
and clipped to 0-255. One node a row: its `embedding`, its `id` (the
row number) and a `category` that is not in the source: 64 values
whose sizes follow a Zipf 0.99, the largest about a fifth of the rows
and the smallest about 0.3%. scale 1000 is the source's 1,000,000
rows; row i is uid 1 + i.

The 256 held-out queries are drawn ONCE from the same mixture
(`query_literals()`); the traffic file holds them as text and a test
regenerates that list byte for byte.

A dataset module gives: SCHEMA, CLASSES, class_of_literal(),
class_range(), write_rdf(). Pure numpy and stdlib: the harness's
parent imports it and must never import jax or the program (the one
question it has for the program, `require_exact_program()`, is asked
in a child).
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

SCHEMA = """
embedding: float32vector @index(vector) .
id: int @index(int) .
category: int @index(int) .
"""

# The deployment's guarantee is an exact answer from `@index(vector)` at
# ANY size. A program gives it only where approximation is something
# the schema asks for. One whose schema language cannot say so lets a
# predicate's size switch the quantized tier on, in the served tier and
# in the postings tier alike: it cannot run this configuration, and
# write_rdf() says so at once instead of letting a run measure answers
# that differ by design.
APPROXIMATE_SCHEMA = "embedding: float32vector @index(vector(ivf)) ."
_PROBE = ("import sys; from dgraph_tpu.models.schema import parse_schema; "
          "parse_schema(sys.argv[1])")
# the checkout this file lies in: benchmark/datasets/sift.py
PROGRAM_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# scale 1000 is the source's own corpus (1M base vectors)
ROWS_PER_SCALE = 1000
DIM = 128
N_CLUSTERS = 1024
N_QUERIES = 256
N_CATEGORIES = 64
CATEGORY_ZIPF = 0.99
FIRST_UID = 1

# the templates hold no uid literal; the generator asks all the same
CLASSES = ("row",)

# the mixture and the held-out queries are the deployment's, not a
# run's: they are drawn from these, never from --seed
_MIXTURE_SEED = 128_000_001
_QUERY_SEED = 128_000_002
# spread of a row around its centre, in component units
_NOISE = 14.0
_BLOCK = 1 << 16

# what a control run may serve in place of the sound corpus: one
# component of 1% of the rows moved by 1, which breaks exactness
VARIANTS = ("off-by-one",)

_CACHE: dict = {}


def class_of_literal(u: int) -> None:
    """No template of this dataset holds a uid literal."""
    return None


def class_range(kind: str, scale: int, facts: dict) -> tuple[int, int]:
    """(first uid, entity count) of a class at this scale."""
    if kind != "row":
        raise ValueError(f"unknown class {kind!r}")
    return FIRST_UID, ROWS_PER_SCALE * scale


def require_exact_program() -> None:
    """Raise unless the program beside this file can spell an
    approximate vector index, which is what makes `@index(vector)`
    exact. Asked once a process, in a CPU child: 0.1 s."""
    if _CACHE.get("exact_program") == PROGRAM_ROOT:
        return
    probe = subprocess.run(
        [sys.executable, "-c", _PROBE, APPROXIMATE_SCHEMA],
        cwd=PROGRAM_ROOT, env=os.environ | {"JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    if probe.returncode != 0:
        raise RuntimeError(
            "this program cannot run the sift configuration: its schema "
            f"refuses `{APPROXIMATE_SCHEMA}`, so `@index(vector)` is not "
            "exact at every size there; "
            + (probe.stderr.strip().splitlines() or ["no message"])[-1])
    _CACHE["exact_program"] = PROGRAM_ROOT


def _centres() -> np.ndarray:
    """(N_CLUSTERS, DIM) float32: SIFT's components are gradient
    histogram bins, mostly small with a long tail to about 200."""
    if "centres" not in _CACHE:
        rng = np.random.default_rng(_MIXTURE_SEED)
        c = rng.gamma(0.9, 34.0, size=(N_CLUSTERS, DIM))
        _CACHE["centres"] = np.minimum(c, 215.0).astype(np.float32)
    return _CACHE["centres"]


def _draw(rng: np.random.Generator, n: int) -> np.ndarray:
    """n rows of the mixture as (n, DIM) uint8, in blocks."""
    centres = _centres()
    out = np.empty((n, DIM), np.uint8)
    for lo in range(0, n, _BLOCK):
        hi = min(n, lo + _BLOCK)
        which = rng.integers(0, N_CLUSTERS, hi - lo)
        x = rng.standard_normal((hi - lo, DIM), dtype=np.float32)
        x *= _NOISE
        x += centres[which]
        np.rint(x, out=x)
        np.clip(x, 0.0, 255.0, out=x)
        out[lo:hi] = x.astype(np.uint8)
    return out


def category_weights() -> np.ndarray:
    w = 1.0 / np.arange(1, N_CATEGORIES + 1, dtype=np.float64) \
        ** CATEGORY_ZIPF
    return w / w.sum()


def corpus(scale: int, seed: int, variant: str = ""
           ) -> tuple[np.ndarray, np.ndarray]:
    """(vectors (n, DIM) uint8, category (n,) int64) of `(scale,
    seed)`; row i is uid FIRST_UID + i and has id i. The last one made
    is kept, so the plain reference does not draw it again."""
    if variant and variant not in VARIANTS:
        raise ValueError(f"unknown dataset variant {variant!r}")
    key = (scale, seed, variant)
    if _CACHE.get("corpus_key") != key:
        n = ROWS_PER_SCALE * scale
        rng = np.random.default_rng(seed)
        vecs = _draw(rng, n)
        cats = rng.choice(N_CATEGORIES, size=n, p=category_weights())
        if variant == "off-by-one":
            vrng = np.random.default_rng([seed, 1])
            rows = vrng.choice(n, size=max(1, n // 100), replace=False)
            comps = vrng.integers(0, DIM, len(rows))
            old = vecs[rows, comps].astype(np.int16)
            vecs[rows, comps] = np.where(old < 255, old + 1, old - 1)
        _CACHE["corpus_key"], _CACHE["corpus"] = key, (vecs, cats)
    return _CACHE["corpus"]


def query_vectors() -> np.ndarray:
    """The 256 held-out queries, (N_QUERIES, DIM) uint8."""
    return _draw(np.random.default_rng(_QUERY_SEED), N_QUERIES)


def literal(vec) -> str:
    """One vector as the text of a float32vector literal."""
    return "[" + ", ".join(str(int(v)) for v in vec) + "]"


def query_literals() -> list[str]:
    """What benchmark/traffic/knn-mix.json lists under `$vec`."""
    return [literal(v) for v in query_vectors()]


def traffic_mix() -> dict:
    """benchmark/traffic/knn-mix.json, whole: the held-out queries are
    written into it by this function and by nothing else
    (`python benchmark/datasets/sift.py` rewrites the file)."""
    vec = {"choice": query_literals()}
    return {
        "loop": "closed", "clients": 8, "bindings": 32,
        "templates": [
            {"name": "knn10", "file": "queries/knn10.gql",
             "params": {"vec": vec}},
            {"name": "knn100", "file": "queries/knn100.gql",
             "params": {"vec": vec}},
            {"name": "knn10_in_category",
             "file": "queries/knn10_in_category.gql",
             "params": {"vec": vec,
                        "c": {"int": [0, N_CATEGORIES - 1]}}},
        ]}


# every component as four bytes of text, "  7 ": a block of rows turns
# into text by ONE fancy index, with no Python loop over components
_TEXT = np.array([f"{v:3d} ".encode() for v in range(256)], dtype="S4")


def write_rdf(out, scale: int, seed: int, variant: str = "") -> dict:
    """Write the corpus's N-Quads to `out`, three a row; -> facts.

    facts: {"rdf": lines written, "edges": {predicate: count},
    "rows", "dim", "seed", "category_sizes"}: what the size report
    needs, and the seed, from which the plain reference
    (sift_plain.py) draws the SOUND corpus again. `variant`
    "off-by-one" moves one component of 1% of the rows by 1: the
    degraded corpus of the control run, never of a measured one.
    Refuses a program that cannot give the deployment's guarantee
    (`require_exact_program()`) before a byte is written."""
    require_exact_program()
    vecs, cats = corpus(scale, seed, variant)
    n = len(vecs)
    raw = getattr(out, "buffer", None)
    if raw is not None:
        out.flush()
    for lo in range(0, n, _BLOCK):
        hi = min(n, lo + _BLOCK)
        body = np.ascontiguousarray(_TEXT[vecs[lo:hi]]).view(
            f"S{4 * DIM}").ravel().tolist()
        text = b"".join(
            b'<0x%x> <embedding> "[%s]" .\n<0x%x> <id> "%d" .\n'
            b'<0x%x> <category> "%d" .\n'
            % (FIRST_UID + i, b, FIRST_UID + i, i, FIRST_UID + i, c)
            for i, b, c in zip(range(lo, hi), body, cats[lo:hi].tolist()))
        if raw is not None:
            raw.write(text)
        else:
            out.write(text.decode())
    # the variant moves components only: the categories are the sound
    # corpus's own
    return {"rdf": 3 * n, "rows": n, "dim": DIM, "seed": seed,
            "edges": {"embedding": n, "id": n, "category": n},
            "category_sizes": np.bincount(
                cats, minlength=N_CATEGORIES).tolist()}


if __name__ == "__main__":
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "traffic", "knn-mix.json")
    with open(path, "w") as f:
        json.dump(traffic_mix(), f, indent=1)
        f.write("\n")
    print(path)
