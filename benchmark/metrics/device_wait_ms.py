"""Device: median `server_latency.device_wait_ns` over the good replies
that made a device call: the time their `device.call` spans
(dgraph_tpu/query/devicecall.py) spent between the jitted call's return
and `block_until_ready`'s: queueing behind other requests' programs,
then the program's own run. In a k-hop cell (a bound `@recurse`,
since PR 34) it runs from joining the rendezvous to the result of the
call the request rode: the call in flight before it, then its own
call's upload, launch, run and the ONE fetch of its counts, for the
thread that launches and for those that ride alike. None where the
program serves no such key (a commit before the span, an `alpha
--no-device`)."""


def read(ctx):
    v = [r["server"]["device_wait_ns"] / 1e6 for r in ctx["replies"]
         if r["good"] and r["server"].get("device_calls", 0) >= 1]
    return ctx["stats"].percentile(v, 50.0) if v else None
