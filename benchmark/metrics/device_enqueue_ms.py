"""Executor: median `server_latency.device_enqueue_ns` over the good
replies that made a device call: operand padding and upload and the
jitted call returning its future, the host's part of a `device.call`
span (dgraph_tpu/query/devicecall.py). In a k-hop cell (a bound
`@recurse`, since PR 34) the phase ends where the request JOINS the
rendezvous: the roots' slots only; the upload and the launch of the
call it rides lie in `device_wait_ms`. None where the key is not
served."""


def read(ctx):
    v = [r["server"]["device_enqueue_ns"] / 1e6 for r in ctx["replies"]
         if r["good"] and r["server"].get("device_calls", 0) >= 1]
    return ctx["stats"].percentile(v, 50.0) if v else None
