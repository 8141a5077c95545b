"""The least bytes a device program has to move: the yardstick of the
kernels' roofline shares.

A cost function is a file of its own, costs/<program>.py, named as XLA
prints the program (`jit_...`), so a later PR costs one more program
by adding a file. It holds

  TEMPLATE            the ONE template whose requests call the program
  least_bytes(sizes)  bytes of one call, from graph_sizes() below

Every program costed so far is memory-bound by statement (sorts and
gathers over uid vectors: no matrix unit work), so its roofline is
bytes over the chip's HBM bandwidth (peaks.json). The bytes are a
LOWER bound: each input and each output counted once at its real,
unpadded length, no intermediate, no second pass of a sort. A share
worked out from them can therefore not pass 100%; one that does means
the sizes are wrong for the traffic that ran.

A program's sizes depend on the request that called it, and a trace
names programs, not requests. So a program is costed only where one
template of the mix calls it, and the roofline metric lists the cells
whose mix has that template; a program that several templates share
(the fused page, `jit_run`) shows in `breakdown` by time only (PERF.md,
list for the tracing issue: named scopes per stage).

`sizes` is what cost functions are fed: the graph's own counts
(films, perfs, named) and, per template, the mean of each
whole-number parameter over the pool's bindings.
"""

from __future__ import annotations

import os
import re


def graph_sizes(dataset, scale: int, facts: dict, pool: list[dict]) -> dict:
    n = {k: v * scale for k, v in dataset.PER_SCALE.items()}
    sizes = {
        "films": n["film"],
        "perfs": int(facts["perfs"]),
        "named": sum(n.values()) + sum(dataset.FIXED.values()),
        "params": {},
    }
    by_t: dict[str, dict[str, list[float]]] = {}
    for e in pool:
        for k, v in e.get("params", {}).items():
            if str(v).isdigit():  # a whole number is a size; a uid is not
                by_t.setdefault(e["name"], {}).setdefault(k, []).append(
                    float(v))
    sizes["params"] = {t: {k: sum(v) / len(v) for k, v in p.items()}
                       for t, p in by_t.items()}
    return sizes


def find(program: str, bench_dir: str, load_module):
    """The cost module of a program, or None when it has none."""
    if not re.fullmatch(r"[A-Za-z0-9_.\-]+", program):
        return None  # the trace's name is a file's name here
    path = os.path.join(bench_dir, "costs", program + ".py")
    return load_module(path) if os.path.isfile(path) else None
