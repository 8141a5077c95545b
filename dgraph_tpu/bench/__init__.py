"""Shared benchmark plumbing.

The load harnesses (tools/dgbench.py, the tools/check.sh load smoke)
drive the same two primitives:

  openloop   the open-loop arrival scheduler + latency/percentile
             summarizers (latency = finish - SCHEDULED arrival, so
             queueing counts — the property closed-loop harnesses
             can't measure)
  workload   the seeded LDBC-SNB-style social-graph generator and
             deterministic mixed read/write op stream

Keeping them here (inside the package, importable from any entry
point) is what lets a regression gate and a capacity probe agree on
what "offered load" and "p99" mean.
"""
