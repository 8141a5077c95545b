"""`device.call`: the one place a request meets the chip.

Every device dispatch of the executor (and `engine/device_cache.py`'s
expand) runs inside one `device_call` block, so the count and the time
of a dispatch are taken at one place:

    with device_call("query_device_sort_page_total", sink=self.lat,
                     program="multisort_page") as dc:
        out = multisort_page(cand, ...)     # enqueue
        res = to_numpy(dc.wait(out))        # wait, then fetch

Three phases on `time.perf_counter_ns`:

  enqueue  block entry -> `wait()` is called: operand padding and
           upload, the jitted call returning its future. Host work.
  wait     -> `jax.block_until_ready` returns: queueing behind other
           requests' programs on the chip, then the program's own run.
  fetch    -> block exit: device-to-host copy, compaction.

A block that dispatched (called `wait`) and raised nothing writes, at
its exit: the site's own counter (names and labels as they always
were: `query_device_*_total`, `query_fused_dispatch_total`,
`query_sharded_expand_total`); a `device.call` span with `family`,
`program`, `enqueue_us`, `wait_us`, `fetch_us`, `out_bytes`; the
request's roll-up (`sink`: engine/db.py's Latency, or None outside a
request); and `device_call_ns_total{family,phase}`. A block that
never dispatched (the callee declined: >32-bit uids, an empty
frontier) counts nothing, as before. Each phase is also a
`jax.profiler.TraceAnnotation` (`device.enqueue`, `device.wait`,
`device.fetch`), so an idle gap of a device profile reads as host
dispatch overhead, queueing or transfer.

Callees that dispatch and fetch in one function (`expand_np`,
`setops.union_many_device`, `bitgraph.sssp_dist`) take `sync=dc.wait`:
a function applied to the dispatched result before it is fetched.
"""

from __future__ import annotations

import time

from dgraph_tpu.utils.metrics import inc_counter
from dgraph_tpu.utils.tracing import span, trace_annotation


def _family(counter: str) -> str:
    """`query_device_sort_page_total` -> `sort_page`,
    `query_fused_dispatch_total` -> `fused_dispatch`."""
    name = counter.removeprefix("query_").removesuffix("_total")
    return name.removeprefix("device_")


class device_call:
    __slots__ = ("_sink", "_counter", "_labels", "_span", "_attrs",
                 "_ann", "_t0", "_t1", "_t2", "_out_bytes")

    def __init__(self, counter: str, labels: dict | None = None, *,
                 sink=None, program: str = ""):
        self._sink = sink
        self._counter = counter
        self._labels = labels
        self._span = span("device.call", family=_family(counter),
                          program=program)

    def _phase(self, name: str | None) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._ann = trace_annotation(name) if name else None
        if self._ann is not None:
            self._ann.__enter__()

    def __enter__(self) -> "device_call":
        self._attrs = self._span.__enter__()
        self._ann = None
        self._t1 = self._t2 = 0
        self._phase("device.enqueue")
        self._t0 = time.perf_counter_ns()
        return self

    def wait(self, out):
        """The dispatched result, once the device has produced it."""
        import jax

        t1 = time.perf_counter_ns()
        self._phase("device.wait")
        out = jax.block_until_ready(out)
        t2 = time.perf_counter_ns()
        self._phase("device.fetch")
        self._t1, self._t2 = t1, t2
        nbytes = getattr(out, "nbytes", None)
        self._out_bytes = int(nbytes) if nbytes is not None else sum(
            int(x.nbytes) for x in jax.tree_util.tree_leaves(out))
        return out

    def __exit__(self, etype, exc, tb) -> None:
        t3 = time.perf_counter_ns()
        self._phase(None)
        if self._t2 and etype is None:
            inc_counter(self._counter, labels=self._labels)
            phases = (("enqueue", self._t1 - self._t0),
                      ("wait", self._t2 - self._t1),
                      ("fetch", t3 - self._t2))
            a = self._attrs
            for phase, ns in phases:
                a[phase + "_us"] = ns // 1000
                inc_counter("device_call_ns_total", ns,
                            labels={"family": a["family"],
                                    "phase": phase})
            a["out_bytes"] = self._out_bytes
            sink = self._sink
            if sink is not None:
                sink.device_calls += 1
                sink.device_enqueue_ns += phases[0][1]
                sink.device_wait_ns += phases[1][1]
                sink.device_fetch_ns += phases[2][1]
        self._span.__exit__(etype, exc, tb)
