"""Device tiles: bytes of bitmap adjacencies ONE chip holds when the
window has closed: the gauge `device_bitadj_chip_bytes` (the fullest
chip's share of a predicate's tile: the in-neighbour matrices and hub
rows of the destination rows it was dealt, engine/device_cache.py; the
whole tile where there is no mesh; 0 once evicted) summed over
predicates. It is what a chip's tile budget is charged, where
`bitadj_bytes`' gauge counts all chips together. None where the gauge
is not served (a program without `alpha --chips`)."""

GAUGE = "device_bitadj_chip_bytes"


def read(ctx):
    v = [v for k, v in ctx["counters_after"].items() if k.startswith(GAUGE)]
    return sum(v) if v else None
