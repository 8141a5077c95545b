"""Executor: its self time, the median over good replies of
`processing_ns` minus the three phases of the request's device calls
(`device_enqueue_ns`, `device_wait_ns`, `device_fetch_ns`): index
probes, planning, host tiers, and eight request threads taking turns
in one interpreter. None where the keys are not served."""


def read(ctx):
    v = [(s["processing_ns"] - s["device_enqueue_ns"]
          - s["device_wait_ns"] - s["device_fetch_ns"]) / 1e6
         for s in (r["server"] for r in ctx["replies"] if r["good"])
         if "device_calls" in s and "processing_ns" in s]
    return ctx["stats"].percentile(v, 50.0) if v else None
