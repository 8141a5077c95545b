"""Device: bytes that left the device for one `shortest` request.
`shortest_fetch_bytes_total` (the one small array a call's landing
thread fetches for all its riders: a row a lane of hops, levels and
at most depth + 1 slots of the path, and the call's own row; added up in
`executor._land_paths`) over `shortest_riders_total`, as deltas over
the window: a mean a request. The path is chosen ON the device from
the levels kept there, so this is tens of bytes where a distance
vector would be 4 B a vertex. None where the program serves one of
the counters not at all or carried no pair in the window."""

BYTES = "shortest_fetch_bytes_total"
RIDERS = "shortest_riders_total"


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if BYTES not in a or RIDERS not in a:
        return None
    riders = a[RIDERS] - b.get(RIDERS, 0)
    if riders <= 0:
        return None
    return (a[BYTES] - b.get(BYTES, 0)) / riders
