"""Device: median `server_latency.device_queue_ns` over the good
replies that made a device call: the stand at the rendezvous
(`query/devicecall.Ride.waited_ns`, the `device.call` span's
`batch_wait_us`), from joining until the call the request rode was
launched, i.e. the rest of the call in flight before it and the
turn-round after; 0 for a request that found the chip free. It lies
INSIDE `device_wait_ms`: their difference is the request's own call
(launch, run, the one fetch) without the queue before it. None where
the program serves no such key (a commit before PR 39, an `alpha
--no-device`)."""


def read(ctx):
    v = [s["device_queue_ns"] / 1e6
         for s in (r["server"] for r in ctx["replies"] if r["good"])
         if s.get("device_calls", 0) >= 1 and "device_queue_ns" in s]
    return ctx["stats"].percentile(v, 50.0) if v else None
