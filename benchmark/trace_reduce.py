#!/usr/bin/env python3
"""From a profiler trace (.xplane.pb) to the numbers the metric
readers take: device busy and idle time, time per XLA program and per
op under the names XLA printed, and the longest idle gaps with what
the host was doing in them.

  python benchmark/trace_reduce.py <trace dir or .xplane.pb> --out f.json

Runs in a process of its own under JAX_PLATFORMS=cpu (it needs jax's
ProfileData reader, nothing else of jax, and the harness's parent
never imports jax). What it reads:

  * device planes: those named /device:TPU:<n> (one per chip);
  * on each, the line "XLA Ops" (one event per op executed, the time
    the op held the device) and the line "XLA Modules" (one event per
    executed program, named as XLA names the jitted function);
  * the host plane /host:CPU: the runtime's own annotations per
    thread, used only to label idle gaps.

busy_s is the union of the op intervals of a chip, averaged over the
chips; window_s is the span from the first to the last event of the
whole trace (host threads are recorded throughout, so this is the
traced interval). Gaps are labelled only as far as the trace allows
today: the narrowest host annotation that covers half the gap or
more. The program's own spans are not on the profiler's clock yet (PERF.md, list for the
tracing issue).
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10


def _union(intervals):
    """Sorted, merged [start, end] intervals."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1][1] = hi
        else:
            out.append([lo, hi])
    return out


def _program_name(name: str) -> str:
    """`jit_fused_rank_page(1234567890)` -> `jit_fused_rank_page`: the
    suffix is a fingerprint that changes with the shapes."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """`%sort.22 = (u32[1052672]{...}, ...) sort(...)` -> `sort.22`:
    XLA prints an op as its whole HLO line."""
    return name.split(" = ", 1)[0].lstrip("%")[:48]


def reduce_profile(pd) -> dict:
    """ProfileData -> the reduced trace (times in seconds)."""
    lo = hi = None
    device_planes = []
    host_lines = []
    names = {}
    for plane in pd.planes:
        lines = list(plane.lines)
        names[plane.name] = [ln.name for ln in lines]
        if DEVICE_PLANE.match(plane.name):
            device_planes.append((plane.name, lines))
        elif plane.name == HOST_PLANE:
            host_lines = lines
    # the traced interval: first to last event of anything recorded
    host_events = []
    for ln in host_lines:
        for e in ln.events:
            if e.duration_ns <= 0:
                continue
            host_events.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
            lo = e.start_ns if lo is None else min(lo, e.start_ns)
            end = e.start_ns + e.duration_ns
            hi = end if hi is None else max(hi, end)

    per_chip_busy = []
    op_time: dict[str, float] = {}
    program_time: dict[str, list] = {}
    first_chip_busy = []
    for _, lines in device_planes:
        ops, modules = [], []
        for ln in lines:
            if ln.name == OPS_LINE:
                ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in ln.events]
            elif ln.name == MODULES_LINE:
                modules = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in ln.events]
        for s, t, _ in ops + modules:
            lo = s if lo is None else min(lo, s)
            hi = t if hi is None else max(hi, t)
        merged = _union([(s, t) for s, t, _ in ops])
        per_chip_busy.append(sum(t - s for s, t in merged) / 1e9)
        if not first_chip_busy:
            first_chip_busy = merged
        starts = [m[0] for m in sorted(modules)]
        by_start = sorted(modules)
        for s, t, name in ops:
            # an op belongs to the program whose event contains it
            k = bisect.bisect_right(starts, s) - 1
            prog = _program_name(by_start[k][2]) if k >= 0 \
                and s < by_start[k][1] else "?"
            key = f"{prog}/{_op_name(name)}"
            op_time[key] = op_time.get(key, 0.0) + (t - s) / 1e9
        for s, t, name in modules:
            rec = program_time.setdefault(_program_name(name), [0.0, 0])
            rec[0] += (t - s) / 1e9
            rec[1] += 1

    n = len(device_planes)
    out = {
        "planes": names,
        "chips": n,
        "window_s": None if lo is None else (hi - lo) / 1e9,
        "busy_s": None,
        "device_ops": [], "programs": [], "idle_gaps": [],
    }
    if n == 0 or lo is None:
        return out
    out["busy_s"] = sum(per_chip_busy) / n
    out["device_ops"] = [
        [name, secs / n] for name, secs in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:3 * TOP]]
    out["programs"] = [
        [name, rec[0] / n, rec[1]] for name, rec in sorted(
            program_time.items(), key=lambda kv: -kv[1][0])[:3 * TOP]]

    # idle gaps of the first chip, longest first
    gaps = []
    edges = [[lo, lo]] + first_chip_busy + [[hi, hi]]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    host_events.sort()
    for length, a, b in gaps[:TOP]:
        # the narrowest annotation that covers at least half the gap:
        # an outer span (a whole request) hides what ran inside it
        best, best_len = "no host annotation", None
        for s, t, name in host_events:
            if s >= b:
                break
            if 2 * (min(t, b) - max(s, a)) >= length and (
                    best_len is None or t - s < best_len):
                best, best_len = name, t - s
        out["idle_gaps"].append([best, length / 1e9])
    return out


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(args.trace))
    with open(args.out, "w") as f:
        json.dump(reduce_profile(pd), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
