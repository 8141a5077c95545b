"""Device: median `server_latency.device_fetch_ns` over the good
replies that made a device call: from `block_until_ready`'s return to
the host array (device-to-host copy, compaction), the last phase of a
`device.call` span (dgraph_tpu/query/devicecall.py). In a k-hop cell
(a bound `@recurse`, since PR 34) the call's result is fetched once
for all its riders inside `device_wait_ms`; what is left here is a
rider taking its own count out of it, microseconds. None where the
key is not served."""


def read(ctx):
    v = [r["server"]["device_fetch_ns"] / 1e6 for r in ctx["replies"]
         if r["good"] and r["server"].get("device_calls", 0) >= 1]
    return ctx["stats"].percentile(v, 50.0) if v else None
