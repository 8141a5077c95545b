"""Deterministic movie-shaped dataset for the golden conformance suite.

Shape mirrors the reference's 21million movie graph
(systest/21million/) at ~1/200 scale: directors -> films -> genres +
starring performances -> actors/characters, with release dates,
ratings, countries and edge facets. Everything derives from a fixed
RNG seed, so goldens are stable across machines.
"""

from __future__ import annotations

import numpy as np

SCHEMA = """
name: string @index(term, exact, trigram) @lang .
aka: [string] @index(term) .
initial_release_date: datetime @index(year) .
rating: float @index(float) .
runtime: int @index(int) .
genre: [uid] @reverse @count .
starring: [uid] @count .
performance.actor: [uid] @reverse .
performance.character: [uid] .
director.film: [uid] @reverse .
country: [uid] .
tagline: string @index(fulltext) .
loc: geo @index(geo) .
"""

N_DIRECTORS = 120
N_FILMS = 1200
N_ACTORS = 900
N_CHARACTERS = 1500
N_GENRES = 24
N_COUNTRIES = 30

GENRES = ["Drama", "Comedy", "Action", "Thriller", "Romance", "Horror",
          "Sci-Fi", "Fantasy", "Documentary", "Animation", "Crime",
          "Adventure", "Mystery", "Western", "Musical", "War", "Family",
          "Biography", "History", "Sport", "Noir", "Short", "News",
          "Reality"]

_WORDS = ["dark", "light", "last", "first", "lost", "hidden", "silent",
          "broken", "golden", "iron", "red", "blue", "wild", "frozen",
          "burning", "secret", "final", "eternal", "fallen", "rising"]
_NOUNS = ["city", "river", "mountain", "dream", "night", "day", "war",
          "love", "house", "road", "storm", "garden", "empire", "king",
          "queen", "shadow", "star", "heart", "world", "game"]


def _uid(kind: str, i: int, scale: int = 1) -> int:
    # bases scale with the dataset so ranges never collide: the gap
    # between adjacent bases is >= 0x10000*scale while the largest
    # entity count grows as ~6600*scale (perfs)
    base = {"director": 0x10000, "film": 0x20000, "actor": 0x40000,
            "character": 0x50000, "genre": 0x60000, "country": 0x70000,
            "perf": 0x80000}[kind]
    return base * scale + i


SEED = 21_000_000


def generate(scale: int = 1, seed: int = SEED) -> tuple[str, list[str]]:
    """-> (schema, nquad lines).

    scale=1 at the default seed is the golden-suite dataset
    (bit-identical across versions: committed expected outputs embed
    its uids and values). scale=800 reproduces the reference's
    21million acceptance regime (systest/21million/test-21million.sh)
    — same shape, ~21.4M RDF. Another `seed` redraws every name,
    value and edge over the same uid layout."""
    rng = np.random.default_rng(seed)
    out: list[str] = []
    n_directors = N_DIRECTORS * scale
    n_films = N_FILMS * scale
    n_actors = N_ACTORS * scale
    n_characters = N_CHARACTERS * scale

    def add(s, p, o, facets=""):
        out.append(f"<{s:#x}> <{p}> {o} {facets}.")

    def name_of(kind, i, rng):
        w = _WORDS[int(rng.integers(len(_WORDS)))]
        n = _NOUNS[int(rng.integers(len(_NOUNS)))]
        return f"{w.title()} {n.title()} {kind.title()} {i}"

    for i in range(N_GENRES):
        add(_uid("genre", i, scale), "name", f'"{GENRES[i]}"')
    for i in range(N_COUNTRIES):
        add(_uid("country", i, scale), "name", f'"Country {i:02d}"')
        lon = round(-180 + 360 * (i / N_COUNTRIES), 3)
        lat = round(-60 + 120 * ((i * 7 % N_COUNTRIES) / N_COUNTRIES), 3)
        add(_uid("country", i, scale), "loc",
            f'"{{\\"type\\":\\"Point\\",\\"coordinates\\":[{lon},{lat}]}}"'
            f"^^<geo:geojson>")
    for i in range(n_directors):
        add(_uid("director", i, scale), "name",
            f'"{name_of("director", i, rng)}"')
    for i in range(n_actors):
        add(_uid("actor", i, scale), "name", f'"{name_of("actor", i, rng)}"')
    for i in range(n_characters):
        add(_uid("character", i, scale), "name",
            f'"{name_of("role", i, rng)}"')

    perf_counter = 0
    for i in range(n_films):
        f = _uid("film", i, scale)
        add(f, "name", f'"{name_of("film", i, rng)}"')
        if i % 3 == 0:
            add(f, "name", f'"Film {i} auf Deutsch"@de')
        year = 1950 + int(rng.integers(75))
        month = 1 + int(rng.integers(12))
        day = 1 + int(rng.integers(28))
        add(f, "initial_release_date",
            f'"{year:04d}-{month:02d}-{day:02d}"')
        add(f, "rating", f'"{round(1 + 9 * float(rng.random()), 2)}"')
        add(f, "runtime", f'"{60 + int(rng.integers(120))}"')
        add(f, "tagline",
            f'"a {_WORDS[i % len(_WORDS)]} tale of '
            f'{_NOUNS[i % len(_NOUNS)]} and {_NOUNS[(i*3+1) % len(_NOUNS)]}"')
        d = int(rng.integers(n_directors))
        add(_uid("director", d, scale), "director.film", f"<{f:#x}>")
        for g in np.unique(rng.integers(0, N_GENRES, 1 + i % 3)):
            add(f, "genre", f"<{_uid('genre', int(g), scale):#x}>")
        add(f, "country",
            f"<{_uid('country', int(rng.integers(N_COUNTRIES)), scale):#x}>")
        for _ in range(2 + int(rng.integers(4))):
            p = _uid("perf", perf_counter, scale)
            perf_counter += 1
            a = int(rng.integers(n_actors))
            c = int(rng.integers(n_characters))
            add(f, "starring", f"<{p:#x}>",
                f"(billing={1 + perf_counter % 9}) ")
            add(p, "performance.actor", f"<{_uid('actor', a, scale):#x}>")
            add(p, "performance.character",
                f"<{_uid('character', c, scale):#x}>")
    # list-valued scalar predicate WITH per-value facets (appended
    # after every earlier rng draw, so the existing goldens' dataset
    # prefix stays bit-identical; ref query0_test.go facets on
    # scalar-list predicates)
    for i in range(0, n_films, 5):
        f = _uid("film", i, scale)
        add(f, "aka", f'"Working Title {i}"',
            f"(kind=\"working\", year={1940 + i % 60}) ")
        add(f, "aka", f'"{_NOUNS[i % len(_NOUNS)].title()} Reborn {i}"',
            "(kind=\"festival\") ")
    return SCHEMA, out
