"""Front end: the share of /query requests that arrived on a
connection already open, 100 x (1 - connections / requests), as the
deltas over the window of `http_connections_total` (one a connection
accepted, counted in server/http.py's `setup()`) and
`http_requests_total` (one a /query answered). Every connection
counts, also those that carried no query (the harness's own scrape of
the counters is one), so the share is a lower bound; 0 where the
window saw no fewer connections than requests, which is what a server
that closes after every reply gives. None where the counter is not
served (a program older than PR 29) or no request was counted."""

CONNS = "http_connections_total"
COUNT = "http_requests_total"


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if CONNS not in a or COUNT not in a:
        return None
    n = a[COUNT] - b.get(COUNT, 0)
    if n <= 0:
        return None
    return max(0.0, 100.0 * (1.0 - (a[CONNS] - b.get(CONNS, 0)) / n))
