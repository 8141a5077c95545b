#!/usr/bin/env python3
"""chip_smoke.py: does dgraph-tpu still start, serve and answer
correctly on a real chip?

Drives the README's main path once, through the entry points a user
calls, and nothing else:

  1. the seeded movie graph of tests/golden/dataset.py at --scale
     (scale 800 = the 21.4M-RDF acceptance regime) is written as RDF;
  2. `python -m dgraph_tpu bulk` loads it into a snapshot (CPU child);
  3. `python -m dgraph_tpu alpha --snapshot` serves it from the chip
     (JAX_PLATFORMS=tpu, so jax itself raises when there is none), and
     an `alpha --no-device` child under JAX_PLATFORMS=cpu serves the
     same snapshot as the plain reference;
  4. the golden-suite queries below go to both over HTTP. Every
     response must be 200, carry no `errors`, and its `data` must be
     byte-identical between chip and reference; a query whose device
     family can be reached at this scale is tagged with the counters
     it must move on the chip child's /debug/prometheus_metrics — a
     correct answer with a flat counter came from the host, and fails
     the run naming the query. The families no query asserts are
     listed with their reasons in NOT_ASSERTED and in the result;
  5. the chip child is restarted on the now-warm persistent compile
     cache and the query set runs again (cold vs warm wall time);
  6. POST /debug/kernelcheck (routed because this script starts its
     alphas with --kernelcheck) compiles every device kernel the
     engine can reach in the process that owns the chip
     (dgraph_tpu/bench/kernelcheck.py).

One process owns the chip at a time. This parent never imports jax or
dgraph_tpu: it writes files, spawns children, speaks HTTP and waits
for every child to exit. The graph-independent kernel checks run in a
data-less alpha while the (host-only) RDF generation and bulk load
proceed beside it, which is also what makes a missing chip fail in
seconds instead of after the load.

On success stdout carries two lines. The first is one JSON object
with what each phase observed (one run's observations — not metrics;
nothing may quote them as a speed), what was cut from the 21.4M-RDF
regime (`reduced`), which device families no query asserted
(`not_asserted`), and "claim": null. The LAST is the verdict the
driver reads, with exactly these keys and the device as jax reported
it in the chip child:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
On any failure: a report on stderr, no result on stdout, exit code 1.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# JAX_PLATFORMS of the children that serve from the chip: jax itself
# raises when there is none, instead of choosing the CPU
PLATFORM = "tpu"

# the regime the issue asks for; anything below it is listed under
# `reduced` in the result
FULL_SCALE = 800
MIN_TRAVERSED_EDGES = 1_000_000

# the predicates the query set traverses: their edge counts say
# whether the tiles on the device were toy tiles
TRAVERSED = ("starring", "performance.actor", "director.film", "genre")

FWD = 'query_device_expand_total{dir="fwd"}'
REV = 'query_device_expand_total{dir="rev"}'

FUSED = "query_fused_dispatch_total"

# (golden query, device counters the chip child MUST move answering
# it). A tag is a counter whose stage clears the executor's gate
# (_device_worth) or the planner's cold prior several times over at
# the default scale. The gate's constant is one dispatch round-trip
# the server measures for itself, 0.63-0.65 ms on a v5e; before
# db.device_dispatch_seconds sampled long enough, a restarted server
# measured 1.75-2.10 ms and routed q049's multisort and forward
# expand and q073's set algebra to the host (PERF.md). Those stages
# sit within 2x of the threshold, so q049 is asked for parity only
# and the two families are asserted on the dedicated queries below,
# whose margins hold at either cost. Each device query costs one or
# two 20-60 s cold compiles, which bounds the list.
GOLDEN = (
    ("q049_ignorereflex", ()),
    ("q058_after_with_sort", ("query_device_sort_page_total",)),
    ("q010_count_filter", ("query_device_count_page_total",)),
    ("q008_multi_sort", (FUSED,)),
    ("q073_regexp_alternation", (FUSED,)),
    ("q004_between", ()),
)

# the golden suite's reverse expansions start from ONE genre; at the
# measured dispatch cost that clears the executor's gate only from
# scale ~600 up. 50,000 actors back to their performances (reverse)
# and on to the characters (forward) clear it at any scale this
# script runs at, several times over.
EXPAND_Q = """
{
  q(func: has(~performance.actor), first: 50000) {
    ~performance.actor { performance.character { uid } }
  }
}
"""

# every film by two keys. `first` is past the paged kernels' window
# (executor._PAGE_MAX_FIRST = 2048), so neither the sort-page nor the
# fused executable takes the block and the whole candidate set goes
# through the multi-key device sort: 1200 x scale films x 2 keys
# against the planner's host lexsort prior is 13x the device's at
# scale 250 even at a 2 ms dispatch.
MULTISORT_Q = """
{
  q(func: has(initial_release_date), orderasc: initial_release_date,
    orderdesc: rating, first: 3000) {
    uid
  }
}
"""

# a root that is the union of eight uid variables, 18,100 x scale uids
# in all: the k-way union co-sort (ops/setops.union_many_device) takes
# four or more operands whose host cost clears the gate — 11x over at
# scale 250 and a 0.65 ms dispatch, 3.5x even at 2 ms.
SETOPS_Q = """
{
  A as var(func: has(performance.actor))
  B as var(func: has(performance.character))
  C as var(func: has(name))
  D as var(func: has(starring))
  E as var(func: has(initial_release_date))
  F as var(func: has(rating))
  G as var(func: has(runtime))
  H as var(func: has(country))
  q(func: uid(A, B, C, D, E, F, G, H), first: 5) { uid }
}
"""

# single-predicate unweighted shortest path: the one shape that has a
# device tier (ops/bitgraph.bfs_paths). Asked for parity: the gate
# keeps this pair on the host (NOT_ASSERTED)
SSSP_Q = """
{
  path as shortest(from: %s, to: %s, depth: 4) {
    starring
  }
  path(func: uid(path)) { uid }
}
"""

# the device families of the issue's list that NO query here asserts,
# and why; printed in the result so a reader of `ok: true` sees what
# it does not cover. Each is answered for parity all the same.
NOT_ASSERTED = {
    "query_device_range_total": (
        "q004_between is asked for parity only: the planner's cold "
        "prior prices the range kernel at the measured dispatch cost "
        "and keeps inequality stages on the host's cached key arrays "
        "until a predicate holds ~600k keys (scale >= 520 for le/ge, "
        ">= 780 for between). The kernel is reached by the "
        "range_select kernel check only."),
    "query_device_orderkeys_total": (
        "cannot move on a clean columnar store: _order_keys' device "
        "arm sits behind the cached key arrays (reached only for "
        "@./@* orders and dirty tablets)."),
    "depth-3 @recurse (x100_recurse_depth3)": (
        "host by design: an unfiltered @recurse needs per-parent edge "
        "lists for its nested output and never takes the batched "
        "device expand."),
    "weighted shortest path (x101_shortest_weighted)": (
        "host by design: a three-predicate shortest path runs the "
        "host Dijkstra; the device's lane search takes one unweighted "
        "predicate (shortest_sssp, below)."),
    "query_device_shortest_total": (
        "shortest_sssp is asked for parity only: a film's one hop to "
        "its performance over `starring` costs the host microseconds "
        "by the gate's reckoning (planner.shortest_costs), under one "
        "dispatch round-trip at every scale. The program is reached "
        "by the bfs_paths kernel check, and under load by the "
        "benchmark's cell pokec-shortest.pairs-c16."),
}


class Fail(Exception):
    """A phase failed; the message says which and why."""


_T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[chip_smoke {time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


# ----------------------------------------------------------------------
# children
# ----------------------------------------------------------------------


class Child:
    """One spawned process, its log file, and a guaranteed stop()."""

    live: list["Child"] = []

    def __init__(self, name: str, argv: list[str], env: dict,
                 workdir: str):
        self.name = name
        self.log_path = os.path.join(workdir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            argv, cwd=HERE, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)
        Child.live.append(self)
        log(f"started {name} (pid {self.proc.pid})")

    def tail(self, n: int = 30) -> str:
        try:
            with open(self.log_path, "rb") as f:
                lines = f.read().decode(errors="replace").splitlines()
        except OSError:
            return ""
        return "\n".join(f"    {self.name}| {ln}" for ln in lines[-n:])

    def stop(self, grace_s: float = 30.0) -> None:
        """SIGINT (alpha drains and exits), then SIGKILL the whole
        process group; returns only once the process is gone, so the
        chip is free for whoever comes next."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGINT)
                self.proc.wait(timeout=grace_s)
            except (subprocess.TimeoutExpired, ProcessLookupError):
                pass
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        self._log.close()
        if self in Child.live:
            Child.live.remove(self)
            log(f"stopped {self.name} (exit {self.proc.returncode})")


def child_env(platform: str) -> dict:
    return dict(os.environ, JAX_PLATFORMS=platform, PYTHONPATH=HERE,
                PYTHONUNBUFFERED="1")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ----------------------------------------------------------------------
# HTTP
# ----------------------------------------------------------------------


def http(url: str, body: bytes | None = None,
         timeout: float = 600.0) -> tuple[int, bytes]:
    req = urllib.request.Request(
        url, data=body,
        headers={"Content-Type": "application/dql"} if body else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def parse_metrics(text: str) -> dict[str, float]:
    """Prometheus text exposition -> {series: value}."""
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, _, val = line.rpartition(" ")
            try:
                out[key] = float(val)
            except ValueError:
                pass
    return out


def metrics(base: str) -> dict[str, float]:
    status, body = http(base + "/debug/prometheus_metrics", timeout=60)
    if status != 200:
        raise Fail(f"GET /debug/prometheus_metrics -> {status}")
    return parse_metrics(body.decode())


class Alpha:
    """A served `python -m dgraph_tpu alpha` child."""

    def __init__(self, name: str, platform: str, workdir: str,
                 snapshot: str = "", flags: tuple = ()):
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        argv = [sys.executable, "-m", "dgraph_tpu", "alpha",
                "--host", "127.0.0.1", "--port", str(self.port),
                *flags]
        if snapshot:
            argv += ["--snapshot", snapshot]
        self.child = Child(name, argv, child_env(platform), workdir)

    def wait_healthy(self, deadline: float) -> dict:
        """Poll /health until it answers; the child exiting first (no
        chip, refused start-up) is the failure, with its own words."""
        name = self.child.name
        while time.monotonic() < deadline:
            if self.child.proc.poll() is not None:
                raise Fail(f"{name} exited {self.child.proc.returncode} "
                           f"before serving:\n{self.child.tail()}")
            try:
                status, body = http(self.base + "/health", timeout=5)
                if status == 200:
                    return json.loads(body)
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(1.0)
        raise Fail(f"{name} not healthy before the deadline:\n"
                   f"{self.child.tail()}")

    def query(self, q: str) -> tuple[int, dict]:
        status, body = http(self.base + "/query", q.encode())
        try:
            return status, json.loads(body)
        except ValueError:
            return status, {"errors": [{"message": body[:300].decode(
                errors="replace")}]}

    def stop(self) -> None:
        self.child.stop()


# ----------------------------------------------------------------------
# the checks (pure: tests feed them canned inputs)
# ----------------------------------------------------------------------


def judge(name: str, must_move: tuple, chip: tuple[int, dict],
          ref: tuple[int, dict], delta: dict[str, float]) -> list[str]:
    """Every way one query can fail; [] when it passed."""
    fails = []
    for who, (status, body) in (("chip", chip), ("reference", ref)):
        if status != 200:
            fails.append(f"{name}: {who} answered HTTP {status}: "
                         f"{json.dumps(body)[:300]}")
        elif body.get("errors"):
            fails.append(f"{name}: {who} response carries errors: "
                         f"{json.dumps(body['errors'])[:300]}")
        elif "data" not in body:
            fails.append(f"{name}: {who} response has no data member")
    if fails:
        return fails
    if json.dumps(chip[1]["data"], sort_keys=True) \
            != json.dumps(ref[1]["data"], sort_keys=True):
        fails.append(f"{name}: chip and --no-device reference disagree")
    for counter in must_move:
        if delta.get(counter, 0) <= 0:
            fails.append(
                f"{name}: answered correctly but {counter} stayed "
                f"flat — the host answered it, not the device")
    return fails


def check_runtime(name: str, health: dict) -> list[str]:
    """What a chip alpha must report about itself at start-up."""
    rt = health.get("runtime") or {}
    fails = []
    dev = rt.get("device") or {}
    if dev.get("platform") != PLATFORM:
        fails.append(f"{name}: runs on {dev or 'no device'}, "
                     f"wanted platform {PLATFORM!r}")
    if not rt.get("native"):
        fails.append(
            f"{name}: native C++ runtime unavailable "
            f"({rt.get('nativeUnavailableReason') or 'no reason given'})"
            " — the Python tokenizer/codec/KV fallbacks are a "
            "different system")
    return fails


def check_kernels(kernels: dict) -> list[str]:
    return [f"kernel {k}: {r.get('error') or 'result differs from its twin'}"
            for k, r in sorted(kernels.items()) if not r.get("ok")]


def verdict(device: dict) -> str:
    """The last stdout line of a passed run: these keys and no others,
    the device as the chip child's jax reported it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]),
        "kind": str(device["kind"]),
        "count": int(device["count"])}})


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------


def build_queries(scale: int):
    """[(name, query text, counters it must move)]."""
    sys.path.insert(0, os.path.join(HERE, "tests"))
    from golden.workload import load_workload

    by_name = dict(load_workload(scale))
    out = [(n, by_name[n], tags) for n, tags in GOLDEN]
    # the reference's own acceptance families: host paths by design
    # (NOT_ASSERTED), asked for parity
    for name in ("x100_recurse_depth3", "x101_shortest_weighted"):
        out.append((name, by_name[name], ()))
    out.append(("expand_rev_fwd", EXPAND_Q, (REV, FWD)))
    out.append(("multisort_films", MULTISORT_Q,
                ("query_device_multisort_total",)))
    out.append(("setops_union8", SETOPS_Q,
                ("query_device_setops_total",)))
    # film 0 stars performance 0 (the generator numbers performances
    # film by film), so the path exists at every scale and seed
    out.append(("shortest_sssp", SSSP_Q % (
        hex(0x20000 * scale), hex(0x80000 * scale)), ()))
    return out


def reduced(gen: dict) -> dict:
    """What this run cut from the issue's regime (scale 800, every
    traversed predicate >= 1M edges); {} when nothing was."""
    out = {}
    if gen["scale"] < FULL_SCALE:
        out["scale"] = {"run": gen["scale"], "full": FULL_SCALE,
                        "rdf": gen["rdf"]}
    small = {p: n for p, n in gen["traversed_edges"].items()
             if n < MIN_TRAVERSED_EDGES}
    if small:
        out["traversed_predicates_under_1M_edges"] = small
    return out


def write_rdf(scale: int, seed: int | None, workdir: str) -> dict:
    sys.path.insert(0, os.path.join(HERE, "tests"))
    from golden.dataset import SEED, generate

    t0 = time.monotonic()
    seed = SEED if seed is None else seed
    schema, quads = generate(scale, seed)
    n = len(quads)
    text = "\n".join(quads)
    del quads
    # a predicate name appears as "> <pred> " on its own lines only
    edges = {p: text.count(f"> <{p}> ") for p in TRAVERSED}
    rdf = os.path.join(workdir, "graph.rdf")
    with open(rdf, "w") as f:
        f.write(text)
        f.write("\n")
    del text
    spath = os.path.join(workdir, "graph.schema")
    with open(spath, "w") as f:
        f.write(schema)
    return {"scale": scale, "seed": seed, "rdf": n,
            "traversed_edges": edges, "rdf_path": rdf,
            "schema_path": spath,
            "seconds": round(time.monotonic() - t0, 1)}


def bulk_load(gen: dict, workdir: str, deadline: float) -> dict:
    snap = os.path.join(workdir, "store.snap")
    t0 = time.monotonic()
    child = Child("bulk", [
        sys.executable, "-m", "dgraph_tpu", "bulk", gen["rdf_path"],
        "--schema", gen["schema_path"], "--out", snap],
        child_env("cpu"), workdir)
    try:
        child.proc.wait(timeout=max(1.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        raise Fail(f"bulk load not done before the deadline:\n"
                   f"{child.tail()}") from None
    finally:
        child.stop()
    if child.proc.returncode != 0 or not os.path.exists(snap):
        raise Fail(f"bulk exited {child.proc.returncode}:\n{child.tail()}")
    dt = time.monotonic() - t0
    return {"snapshot": snap, "seconds": round(dt, 1),
            "snapshot_bytes": os.path.getsize(snap),
            "rdf_per_s_incl_snapshot_write": round(gen["rdf"] / dt)}


def cache_entries(path: str) -> int:
    try:
        return sum(1 for e in os.scandir(path) if e.is_file())
    except OSError:
        return 0


def query_pass(label: str, chip: Alpha, queries,
               ref_answers: dict) -> dict:
    """The query set against one chip alpha: per-query counter deltas,
    the failures, and the wall time of the chip's answers alone."""
    fails, per_query, wall = [], {}, 0.0
    after = metrics(chip.base)
    for name, q, must_move in queries:
        before = after
        t0 = time.monotonic()
        got = chip.query(q)
        dt = time.monotonic() - t0
        wall += dt
        after = metrics(chip.base)
        delta = {k: after[k] - before.get(k, 0) for k in after
                 if k.startswith(("query_device_", "query_fused_",
                                  "query_sharded_"))
                 and after[k] != before.get(k, 0)}
        per_query[name] = {"seconds": round(dt, 3), "moved": delta}
        fails += [f"[{label}] {f}" for f in judge(
            name, must_move, got, ref_answers[name], delta)]
    return {"wall_s": round(wall, 2), "queries": per_query,
            "fails": fails,
            "dispatch_seconds": after.get("device_dispatch_seconds"),
            "device_cache_bytes": after.get("device_cache_bytes", 0),
            "device_cache_evictions":
                after.get("device_cache_evictions", 0)}


def kernelcheck(alpha: Alpha, params: str, deadline: float) -> dict:
    """{kernel: result} from the alpha's own process, at the serving
    shapes."""
    status, body = http(
        f"{alpha.base}/debug/kernelcheck?{params}", b"",
        timeout=max(1.0, deadline - time.monotonic()))
    if status != 200:
        raise Fail(f"POST /debug/kernelcheck?{params} -> {status}: "
                   f"{body[:500]!r}")
    report = json.loads(body)
    if report.get("tiny"):
        raise Fail(f"kernel sweep ran at toy shapes: {body[:200]!r}")
    return report["kernels"]


def smoke(args, workdir: str) -> dict:
    deadline = _T0 + args.budget
    report: dict = {}
    fails: list[str] = []

    # -- a data-less alpha takes the chip FIRST: a missing chip fails
    # here, seconds in; then it runs the graph-independent kernel
    # checks while the parent generates and bulk-loads beside it ------
    probe = Alpha("alpha-kernels", PLATFORM, workdir,
                  flags=("--kernelcheck",))
    health = probe.wait_healthy(min(deadline, time.monotonic() + 180))
    device = (health.get("runtime") or {}).get("device")
    fails += check_runtime("alpha-kernels", health)
    if fails:
        raise Fail("\n".join(fails))
    report["device"] = device
    log(f"chip child reports {device}, native=True, compile cache "
        f"{health['runtime']['compileCache']}")
    cache_dir = health["runtime"]["compileCache"]
    report["compile_cache"] = {"dir": cache_dir,
                               "entries_at_start": cache_entries(cache_dir)}
    kc_synth: dict = {}

    def _kernels():
        try:
            kc_synth["kernels"] = kernelcheck(
                probe, "checks=bfs_digest_xla,bfs_traverse,bfs_paths,"
                "fused_rank_page,setops_cosort,knn_exact", deadline)
        except Exception as e:  # noqa: BLE001 — surfaced after join
            kc_synth["error"] = f"{type(e).__name__}: {e}"

    kc_thread = threading.Thread(target=_kernels, daemon=True)
    kc_thread.start()

    gen = write_rdf(args.scale, args.seed, workdir)
    log(f"generated {gen['rdf']} RDF at scale {gen['scale']} in "
        f"{gen['seconds']}s; traversed edges {gen['traversed_edges']}")
    report["data"] = {k: gen[k] for k in (
        "scale", "seed", "rdf", "traversed_edges", "seconds")}
    report["reduced"] = reduced(gen)
    if report["reduced"]:
        log(f"WARNING: below the issue's regime: {report['reduced']}")
    report["not_asserted"] = NOT_ASSERTED
    bulk = bulk_load(gen, workdir, deadline)
    os.unlink(gen["rdf_path"])
    log(f"bulk-loaded in {bulk['seconds']}s "
        f"({bulk['rdf_per_s_incl_snapshot_write']} RDF/s incl. "
        f"snapshot write)")
    report["bulk"] = {k: v for k, v in bulk.items() if k != "snapshot"}

    kc_thread.join(timeout=max(1.0, deadline - time.monotonic()))
    probe.stop()
    if kc_thread.is_alive() or "error" in kc_synth:
        raise Fail("kernel sweep did not finish: "
                   + kc_synth.get("error", "deadline"))
    report["kernels"] = kc_synth["kernels"]

    # -- the served graph: chip child and plain reference side by side
    queries = build_queries(args.scale)
    ref = Alpha("alpha-reference", "cpu", workdir,
                snapshot=bulk["snapshot"], flags=("--no-device",))
    chip = Alpha("alpha-chip-cold", PLATFORM, workdir,
                 snapshot=bulk["snapshot"], flags=("--kernelcheck",))
    t0 = time.monotonic()
    ref.wait_healthy(deadline)
    health = chip.wait_healthy(deadline)
    report["snapshot_load_s"] = round(time.monotonic() - t0, 1)
    fails += check_runtime("alpha-chip-cold", health)
    log(f"both alphas serving after {report['snapshot_load_s']}s")

    ref_answers = {name: ref.query(q) for name, q, _ in queries}
    cold = query_pass("cold", chip, queries, ref_answers)
    fails += cold.pop("fails")
    cold["cache_entries_after"] = cache_entries(cache_dir)
    report["cold"] = cold
    log(f"cold pass: {cold['wall_s']}s, tiles resident "
        f"{cold['device_cache_bytes']:.0f} B, evictions "
        f"{cold['device_cache_evictions']:.0f}")
    if cold["device_cache_bytes"] <= 0:
        fails.append("device_cache_bytes is 0 after the query set: "
                     "no tile ever reached the device")
    if cold["cache_entries_after"] <= 0:
        fails.append(f"compile cache {cache_dir} is empty after the "
                     "cold pass: the persistent cache is not working")

    report["kernels"].update(kernelcheck(
        chip, "checks=sssp_dist,range_select&pred=starring",
        deadline))
    fails += check_kernels(report["kernels"])
    chip.stop()

    # -- a restarted server on the now-warm persistent cache ----------
    chip = Alpha("alpha-chip-warm", PLATFORM, workdir,
                 snapshot=bulk["snapshot"])
    health = chip.wait_healthy(deadline)
    fails += check_runtime("alpha-chip-warm", health)
    warm = query_pass("warm", chip, queries, ref_answers)
    fails += warm.pop("fails")
    warm["cache_entries_after"] = cache_entries(cache_dir)
    report["warm"] = warm
    log(f"warm pass (restarted server): {warm['wall_s']}s against "
        f"{cold['wall_s']}s cold; cache entries "
        f"{report['compile_cache']['entries_at_start']} -> "
        f"{cold['cache_entries_after']} -> "
        f"{warm['cache_entries_after']}")
    chip.stop()
    ref.stop()

    report["seconds"] = round(time.monotonic() - _T0, 1)
    if fails:
        report["failures"] = fails
        raise Fail("\n".join(fails), report)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=250,
                    help="dataset scale; 800 is the 21.4M-RDF regime. "
                         "The default (6.7M RDF) is what generate + "
                         "bulk + two snapshot loads + ~5 min of cold "
                         "compiles fit into 1200 s with a quarter to "
                         "spare: scale 300 took 955 s on a v5e host")
    ap.add_argument("--seed", type=int, default=None,
                    help="dataset seed (the golden suite's by default)")
    ap.add_argument("--budget", type=float, default=1150.0,
                    help="seconds before the run gives up (non-zero)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(HERE, "dgraph_tpu")):
        print("chip_smoke.py needs the dgraph-tpu checkout around it",
              file=sys.stderr)
        return 1

    def _term(signum, _frame):
        raise Fail(f"signal {signum}")

    signal.signal(signal.SIGTERM, _term)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        report = smoke(args, workdir)
    except Fail as e:
        log("FAILED:\n" + str(e.args[0]))
        if len(e.args) > 1:
            print(json.dumps({"ok": False, **e.args[1]}), file=sys.stderr)
        return 1
    finally:
        for child in list(Child.live):
            child.stop(grace_s=5.0)
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"observations": report, "claim": None}))
    print(verdict(report["device"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
