"""The golden query suite as a workload over the SCALED movie graph.

The committed goldens validate scale 1; the same query strings run at
any `generate(scale)` once their uid literals are remapped to the
scaled uid bases. Shared by chip_smoke.py (the queries over HTTP
against a served snapshot) and tools/mesh_smoke.py (the same queries
in-process over a mesh). Pure stdlib on purpose: chip_smoke.py's parent
process must import this without importing jax or dgraph_tpu.
"""

from __future__ import annotations

import os
import re

_UID_BASES = (0x80000, 0x70000, 0x60000, 0x50000, 0x40000,
              0x20000, 0x10000)

RECURSE_Q = """
{
  r(func: uid(%s)) @recurse(depth: 3) {
    name
    director.film
    starring
    performance.actor
  }
}
"""

SHORTEST_Q = """
{
  path as shortest(from: %s, to: %s, depth: 8) {
    director.film
    starring
    performance.actor
  }
  path(func: uid(path)) { name }
}
"""


def remap_uids(q: str, scale: int) -> str:
    """Rewrite scale-1 uid literals (base + index) to the scaled uid
    space so the workload touches real entities at any scale."""

    def sub(m):
        u = int(m.group(0), 16)
        for base in _UID_BASES:
            if u >= base and u - base < 0x10000:
                return hex(base * scale + (u - base))
        return m.group(0)

    return re.sub(r"0x[0-9a-fA-F]+", sub, q)


def load_workload(scale: int) -> list[tuple[str, str]]:
    """[(name, query)] — every golden query remapped to `scale`, plus
    a depth-3 @recurse and a multi-predicate shortest path (the
    reference's own acceptance families, systest/21million/queries)."""
    qdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "queries")
    out = []
    for fn in sorted(os.listdir(qdir)):
        if fn.endswith(".gql"):
            with open(os.path.join(qdir, fn)) as f:
                out.append((fn[:-4], remap_uids(f.read(), scale)))
    film0 = hex(0x20000 * scale)
    director0 = hex(0x10000 * scale)
    actor16 = hex(0x40000 * scale + 16)
    out.append(("x100_recurse_depth3", RECURSE_Q % film0))
    out.append(("x101_shortest_weighted",
                SHORTEST_Q % (director0, actor16)))
    return out
