"""Front end: what server/http.py's own clock says a /query spends
outside the engine: the deltas over the window of
`http_request_ns_total{phase="pre"}` (entry to the call into the
engine: body, admission, the read lock) and `{phase="post"}` (the
engine's return to the last byte written), over the delta of
`http_requests_total`. A mean. None where the counters are not
served."""

PRE = 'http_request_ns_total{phase="pre"}'
POST = 'http_request_ns_total{phase="post"}'
COUNT = "http_requests_total"


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if COUNT not in a or PRE not in a or POST not in a:
        return None
    n = a[COUNT] - b.get(COUNT, 0)
    if n <= 0:
        return None
    ns = a[PRE] - b.get(PRE, 0) + a[POST] - b.get(POST, 0)
    return ns / n / 1e6
