"""Prometheus text-exposition golden tests: TYPE lines, cumulative
`_bucket` counts, `_sum`/`_count`, and text-format 0.0.4 label-value
escaping (a quote in a label value must not emit a malformed series).
"""

import textwrap

from dgraph_tpu.utils import metrics


def _render_without_memory() -> str:
    """render_prometheus minus the environment-dependent process
    gauges (collect_memory_gauges reads /proc; collect_runtime_gauges
    samples threads/GC/fds/uptime, sums the CPU time of the handler
    threads an earlier test's server left, and polls the gauges whose
    owners asked for it, watch_gauge, in a process that imported
    them): the rest is exact."""
    sampled = ("memory_", "process_", "http_handler_cpu_ns_total",
               *metrics._POLLED)
    lines = [ln for ln in metrics.render_prometheus().splitlines()
             if not any(name in ln for name in sampled)]
    return "\n".join(lines) + "\n"


def test_render_prometheus_golden():
    metrics.reset()
    metrics.inc_counter("dgraph_num_queries_total", 3)
    metrics.inc_counter("dgraph_queries_shed_total",
                        labels={"reason": "overload"})
    metrics.set_gauge("dgraph_pending_queries", 2)
    # one observation per interesting bucket edge: 0.05 -> first
    # bucket (le 0.1); 3 -> le 5; 99999 -> +Inf only
    metrics.observe("dgraph_query_latency_ms", 0.05)
    metrics.observe("dgraph_query_latency_ms", 3)
    metrics.observe("dgraph_query_latency_ms", 99999)
    want = textwrap.dedent("""\
        # TYPE dgraph_num_queries_total counter
        dgraph_num_queries_total 3
        # TYPE dgraph_queries_shed_total counter
        dgraph_queries_shed_total{reason="overload"} 1
        # TYPE dgraph_pending_queries gauge
        dgraph_pending_queries 2
        # TYPE dgraph_query_latency_ms histogram
        dgraph_query_latency_ms_bucket{le="0.1"} 1
        dgraph_query_latency_ms_bucket{le="0.5"} 1
        dgraph_query_latency_ms_bucket{le="1"} 1
        dgraph_query_latency_ms_bucket{le="2"} 1
        dgraph_query_latency_ms_bucket{le="5"} 2
        dgraph_query_latency_ms_bucket{le="10"} 2
        dgraph_query_latency_ms_bucket{le="25"} 2
        dgraph_query_latency_ms_bucket{le="50"} 2
        dgraph_query_latency_ms_bucket{le="100"} 2
        dgraph_query_latency_ms_bucket{le="250"} 2
        dgraph_query_latency_ms_bucket{le="500"} 2
        dgraph_query_latency_ms_bucket{le="1000"} 2
        dgraph_query_latency_ms_bucket{le="2500"} 2
        dgraph_query_latency_ms_bucket{le="5000"} 2
        dgraph_query_latency_ms_bucket{le="10000"} 2
        dgraph_query_latency_ms_bucket{le="+Inf"} 3
        dgraph_query_latency_ms_count 3
        dgraph_query_latency_ms_sum 100002.05
        """)
    assert _render_without_memory() == want
    metrics.reset()


def test_label_value_escaping():
    metrics.reset()
    metrics.set_gauge("dgraph_pending_queries", 1,
                      labels={"q": 'say "hi"\\path\nnext'})
    line = next(ln for ln in _render_without_memory().splitlines()
                if ln.startswith("dgraph_pending_queries{"))
    # text-format 0.0.4: backslash, quote and newline escaped
    assert line == ('dgraph_pending_queries'
                    '{q="say \\"hi\\"\\\\path\\nnext"} 1')
    metrics.reset()


def test_counters_snapshot_diff():
    metrics.reset()
    before = metrics.counters_snapshot()
    metrics.inc_counter("dgraph_num_queries_total")
    metrics.inc_counter("query_colvar_hits_total", 4)
    delta = metrics.counters_delta(before)
    assert delta == {"dgraph_num_queries_total": 1,
                     "query_colvar_hits_total": 4}
    # zero-movement counters are omitted from the profile diff
    assert metrics.counters_delta(metrics.counters_snapshot()) == {}
    metrics.reset()


def test_runtime_gauges_in_exposition():
    """collect_runtime_gauges: fds, threads, GC gen counts/collections
    and uptime ride the same exposition as the memory gauges."""
    metrics.reset()
    text = metrics.render_prometheus()
    assert "# TYPE process_threads gauge" in text
    assert "process_uptime_seconds" in text
    for gen in ("0", "1", "2"):
        assert f'process_gc_collections{{gen="{gen}"}}' in text
        assert f'process_gc_objects{{gen="{gen}"}}' in text
    # Linux container: /proc fd count is available
    assert "process_open_fds" in text
    snap = metrics.gauges_snapshot()
    assert snap["process_threads"] >= 1
    assert snap["process_uptime_seconds"] >= 0
    metrics.reset()
