"""The seeded movie graph: the benchmark's own copy of the generator.

Shape of the reference's 21million movie graph (systest/21million):
directors -> films -> genres + starring performances -> actors and
characters, with release dates, ratings, countries, geo points and
edge facets. scale 800 is the 21.4M-RDF regime. Everything is drawn
from the seed over one fixed uid layout, so `(scale, seed)` names a
graph and the traffic generator can bind a template to entities that
exist without asking a server.

This is a copy of tests/golden/dataset.py (same draws in the same
order, so the same `(scale, seed)` gives the same lines), kept here
because later PRs may change tests/ and may not change the yardstick.
It streams its lines to a file object, so a bulk loader reading the
other end of a pipe works beside it.

A dataset module gives: SCHEMA, CLASSES, class_of_literal(),
class_range(), write_rdf(). Pure numpy and stdlib: the harness's parent imports it and
must never import jax or the program.
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

# the scale of the source's own graph (21.4M RDF)
FULL_SCALE = 800

# entities per unit of scale; genres and countries do not scale
PER_SCALE = {"director": 120, "film": 1200, "actor": 900,
             "character": 1500}
FIXED = {"genre": 24, "country": 30}

# uid of entity i of a class is BASES[class] * scale + i; a scale-1
# literal in a query template is BASES[class] + i with i < 0x10000
BASES = {"director": 0x10000, "film": 0x20000, "actor": 0x40000,
         "character": 0x50000, "genre": 0x60000, "country": 0x70000,
         "perf": 0x80000}
CLASSES = tuple(BASES)

GENRES = ["Drama", "Comedy", "Action", "Thriller", "Romance", "Horror",
          "Sci-Fi", "Fantasy", "Documentary", "Animation", "Crime",
          "Adventure", "Mystery", "Western", "Musical", "War", "Family",
          "Biography", "History", "Sport", "Noir", "Short", "News",
          "Reality"]

WORDS = ["dark", "light", "last", "first", "lost", "hidden", "silent",
         "broken", "golden", "iron", "red", "blue", "wild", "frozen",
         "burning", "secret", "final", "eternal", "fallen", "rising"]
NOUNS = ["city", "river", "mountain", "dream", "night", "day", "war",
         "love", "house", "road", "storm", "garden", "empire", "king",
         "queen", "shadow", "star", "heart", "world", "game"]

# what a control run may serve in place of the sound graph: each
# breaks the configuration's guarantee of exact answers
VARIANTS = ("rating-1dp",)


def class_of_literal(u: int) -> tuple[str, int] | None:
    """(class, index) of a scale-1 uid literal, None if it is none."""
    for kind, base in sorted(BASES.items(), key=lambda kv: -kv[1]):
        if base <= u < base + 0x10000:
            return kind, u - base
    return None


def class_range(kind: str, scale: int, facts: dict) -> tuple[int, int]:
    """(first uid, entity count) of a class at this scale."""
    if kind == "perf":
        n = int(facts["perfs"])
    elif kind in FIXED:
        n = FIXED[kind]
    else:
        n = PER_SCALE[kind] * scale
    return BASES[kind] * scale, n


def write_rdf(out, scale: int, seed: int, variant: str = "") -> dict:
    """Write the graph's N-Quads to `out`, one a line; -> facts.

    facts: {"rdf": lines written, "perfs": performances, "edges":
    {predicate: count}, and per film "name", "runtime", and "rating"
    and "date" (yyyymmdd) of the SOUND graph -- what the size report
    and the plain reference (movies_plain.py) need, JSON-serialisable.
    `variant` "rating-1dp" rounds every rating to one decimal: the
    degraded graph of the control run, never of a measured one."""
    if variant and variant not in VARIANTS:
        raise ValueError(f"unknown dataset variant {variant!r}")
    rating_digits = 1 if variant == "rating-1dp" else 2
    rng = np.random.default_rng(seed)
    n_directors = PER_SCALE["director"] * scale
    n_films = PER_SCALE["film"] * scale
    n_actors = PER_SCALE["actor"] * scale
    n_characters = PER_SCALE["character"] * scale
    b = {k: v * scale for k, v in BASES.items()}
    n_words, n_nouns = len(WORDS), len(NOUNS)
    n_lines = 0
    edges = {"starring": 0, "performance.actor": 0, "genre": 0,
             "director.film": 0}
    buf: list[str] = []

    def flush():
        nonlocal n_lines
        n_lines += len(buf)
        out.write("\n".join(buf))
        out.write("\n")
        buf.clear()

    def name_of(kind, i):
        w = WORDS[int(rng.integers(n_words))]
        n = NOUNS[int(rng.integers(n_nouns))]
        return f"{w.title()} {n.title()} {kind} {i}"

    for i in range(FIXED["genre"]):
        buf.append(f'<{b["genre"] + i:#x}> <name> "{GENRES[i]}" .')
    n_countries = FIXED["country"]
    for i in range(n_countries):
        c = b["country"] + i
        buf.append(f'<{c:#x}> <name> "Country {i:02d}" .')
        lon = round(-180 + 360 * (i / n_countries), 3)
        lat = round(-60 + 120 * ((i * 7 % n_countries) / n_countries), 3)
        buf.append(
            f'<{c:#x}> <loc> "{{\\"type\\":\\"Point\\",\\"coordinates\\":'
            f'[{lon},{lat}]}}"^^<geo:geojson> .')
    for kind, label, n in (("director", "Director", n_directors),
                           ("actor", "Actor", n_actors),
                           ("character", "Role", n_characters)):
        base = b[kind]
        for i in range(n):
            buf.append(f'<{base + i:#x}> <name> "{name_of(label, i)}" .')
        flush()

    perf_counter = 0
    ratings, dates, names, runtimes = [], [], [], []
    for i in range(n_films):
        f = f"<{b['film'] + i:#x}>"
        names.append(name_of("Film", i))
        buf.append(f'{f} <name> "{names[-1]}" .')
        if i % 3 == 0:
            buf.append(f'{f} <name> "Film {i} auf Deutsch"@de .')
        year = 1950 + int(rng.integers(75))
        month = 1 + int(rng.integers(12))
        day = 1 + int(rng.integers(28))
        buf.append(f'{f} <initial_release_date> '
                   f'"{year:04d}-{month:02d}-{day:02d}" .')
        rating = round(1 + 9 * float(rng.random()), 2)
        buf.append(f'{f} <rating> "{round(rating, rating_digits)}" .')
        ratings.append(rating)
        dates.append(year * 10000 + month * 100 + day)
        runtimes.append(60 + int(rng.integers(120)))
        buf.append(f'{f} <runtime> "{runtimes[-1]}" .')
        buf.append(
            f'{f} <tagline> "a {WORDS[i % n_words]} tale of '
            f'{NOUNS[i % n_nouns]} and {NOUNS[(i * 3 + 1) % n_nouns]}" .')
        d = int(rng.integers(n_directors))
        buf.append(f"<{b['director'] + d:#x}> <director.film> {f} .")
        edges["director.film"] += 1
        for g in np.unique(rng.integers(0, FIXED["genre"], 1 + i % 3)):
            buf.append(f"{f} <genre> <{b['genre'] + int(g):#x}> .")
            edges["genre"] += 1
        buf.append(f"{f} <country> "
                   f"<{b['country'] + int(rng.integers(n_countries)):#x}> .")
        for _ in range(2 + int(rng.integers(4))):
            p = f"<{b['perf'] + perf_counter:#x}>"
            perf_counter += 1
            a = int(rng.integers(n_actors))
            c = int(rng.integers(n_characters))
            buf.append(f"{f} <starring> {p} "
                       f"(billing={1 + perf_counter % 9}) .")
            buf.append(f"{p} <performance.actor> <{b['actor'] + a:#x}> .")
            buf.append(f"{p} <performance.character> "
                       f"<{b['character'] + c:#x}> .")
        if len(buf) > 200_000:
            flush()
    edges["starring"] = edges["performance.actor"] = perf_counter
    for i in range(0, n_films, 5):
        f = f"<{b['film'] + i:#x}>"
        buf.append(f'{f} <aka> "Working Title {i}" '
                   f'(kind="working", year={1940 + i % 60}) .')
        buf.append(f'{f} <aka> "{NOUNS[i % n_nouns].title()} Reborn {i}" '
                   f'(kind="festival") .')
    flush()
    return {"rdf": n_lines, "perfs": perf_counter, "edges": edges,
            "rating": ratings, "date": dates,
            "name": names, "runtime": runtimes}
