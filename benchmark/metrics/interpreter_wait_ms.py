"""Executor: the time a request's thread was neither computing nor
waiting for the chip, a mean a request over the window. The wall time
the handler threads spent in requests (`http_request_ns_total`, all
three phases: `server/http.py` `_post_query`) less the CPU time of
those threads (`http_handler_cpu_ns_total`: each thread's own CPU
clock, read at the scrape and as a thread comes and goes,
`utils/metrics.watch_thread_cpu`: no request reads a clock for it)
less what they waited for the device (`device_call_ns_total`,
`phase="wait"`, every family), over `http_requests_total`, all as
deltas over the window. A request runs on one thread from the
handler's entry to its last byte, so what is left is that thread
runnable and not running: waiting for the interpreter's lock behind
the other request threads (and, rarely, for a lock of the program's,
the socket's buffer, or the CPU itself). Two things read it low: the
CPU a thread burns INSIDE a device wait (the thread that lands a
rendezvous' call launches the next) is subtracted twice, and so is
what it burns outside the handler's marks (`http.server` reading the
request line and headers): a cell that never queues for the
interpreter reads under 0. None where a counter is not served (a
program older than PR 39) or no request completed."""

WALL = "http_request_ns_total{"
CPU = "http_handler_cpu_ns_total"
WAIT = ("device_call_ns_total{", 'phase="wait"')
COUNT = "http_requests_total"


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if CPU not in a or COUNT not in a:
        return None
    n = a[COUNT] - b.get(COUNT, 0)
    if n <= 0:
        return None

    def moved(keep):
        return sum(v - b.get(k, 0) for k, v in a.items() if keep(k))

    wall = moved(lambda k: k.startswith(WALL))
    wait = moved(lambda k: k.startswith(WAIT[0]) and WAIT[1] in k)
    return (wall - (a[CPU] - b.get(CPU, 0)) - wait) / n / 1e6
