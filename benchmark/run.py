#!/usr/bin/env python3
"""The benchmark: one cell of BENCHMARK.json, once.

  python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                          --trace <0|1>

Every run is a new process. It finds the cell's configuration, traffic
mix, dataset and metric readers BY NAME under this directory (this
file holds no name of a cell, a query or a metric), then

  1. starts the chip child (serve_chip.py, which takes the chip and
     then runs the program's own `alpha`): no chip, or fewer chips
     than the cell asks for, ends the run here, in seconds, non-zero,
     with nothing on stdout;
  2. builds or finds the seed's snapshot under .cache/<config>/<seed>/
     (the dataset module writes the RDF, `python -m dgraph_tpu bulk`
     loads it in a JAX_PLATFORMS=cpu child);
  3. serves it from the chip child and, when the cache holds no
     reference for this seed and traffic, from an `alpha --no-device`
     child under JAX_PLATFORMS=cpu beside it, which answers every
     query of the pool once (the plain reference: SHA-256 of `data`);
  4. warms up: every pool query once, in order, on one connection;
  5. measures for --seconds: the mix's clients in a closed loop over
     HTTP, each reply timed from the request's first byte to the
     reply's last; with --trace 1 a device trace of a slice of it;
  6. compares every reply of the warm-up and of the window with the
     reference, stops every child, and prints the result.

stdout's LAST line is one JSON object with the keys `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` in a
traced run that has one), then `compared`: each number `correct` was
decided by, beside its limit (stderr's last lines say the same).
Earlier lines say what each phase took, how late the client threads
ran, the gate's constant, and each number compared beside its limit.
With --trace 0 `metrics` holds the cell's end-to-end metrics, with
--trace 1 its per-layer metrics.

This parent never imports jax or the program: one process holds the
chip. `--rehearse <scale>` with JAX_PLATFORMS=cpu in the caller's
environment walks the whole flow on the CPU at a tiny scale: its
`device` says cpu, its metrics are the counted ones only (no time, no
rate), and its caches go to a temporary directory. Without both, no
chip is a failure. `--control <variant>` serves a degraded graph
(datasets/<dataset>.py VARIANTS) against the sound reference: such a
run must print `correct: false`.
"""

from __future__ import annotations

import argparse
import gc
import hashlib
import http.client
import importlib.util
import json
import os
import queue
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
_T0 = time.monotonic()

# a trace covers this share of the window, from this share in, and at
# most this long: traces are large and exporting one stalls the host
TRACE_FROM, TRACE_SHARE, TRACE_MAX_S = 0.3, 0.3, 5.0
# a run gives up by itself (non-zero, nothing on stdout) before the
# 1200 s the contract allows the first, compiling run of a cell
GIVE_UP_S = 1150.0


class Fail(Exception):
    """The run cannot give a result; the message says why."""


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_SAID: list[str] = []


def say(msg: str) -> None:
    """An earlier line of stdout: what a reader of the result needs.
    Held back until the result is due, so that a run that fails has
    printed nothing there; stderr has it at once."""
    log(msg)
    _SAID.append(msg)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").removesuffix(".py").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise Fail(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------------
# children (process discipline copied from chip_smoke.py)
# ----------------------------------------------------------------------


class Child:
    """One spawned process, its log file, and a guaranteed stop()."""

    live: list["Child"] = []

    def __init__(self, name: str, argv: list[str], env: dict,
                 logdir: str, pass_fds: tuple = ()):
        self.name = name
        self.log_path = os.path.join(logdir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
            pass_fds=pass_fds)
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
        """SIGINT (alpha drains and exits), then SIGKILL the process
        group; returns only once the process is gone."""
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


class Background(threading.Thread):
    """A thread whose result, or failure, the caller collects."""

    def __init__(self, what: str, fn, *args):
        super().__init__(daemon=True)
        self.what, self._fn, self._args = what, fn, args
        self.value = self.error = None
        self.start()

    def run(self) -> None:
        try:
            self.value = self._fn(*self._args)
        except Exception as e:  # noqa: BLE001 -- raised by result()
            self.error = e

    def result(self, timeout: float | None = None):
        self.join(timeout)
        if self.is_alive():
            raise Fail(f"{self.what}: not done in time")
        if self.error is not None:
            raise Fail(f"{self.what}: {type(self.error).__name__}: "
                       f"{self.error}")
        return self.value


def child_env(platform: str) -> dict:
    return dict(os.environ, JAX_PLATFORMS=platform, PYTHONPATH=ROOT,
                PYTHONUNBUFFERED="1")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_get(port: int, path: str, timeout: float = 60.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def wait_healthy(port: int, child: Child, deadline: float) -> dict:
    while time.monotonic() < deadline:
        if child.proc.poll() is not None:
            raise Fail(f"{child.name} exited {child.proc.returncode} "
                       f"before serving:\n{child.tail()}")
        try:
            status, body = http_get(port, "/health", timeout=5)
            # `alpha` fills `runtime` a moment after it starts to
            # listen; a reply without it came too early
            if status == 200 and json.loads(body).get("runtime"):
                return json.loads(body)
        except (OSError, http.client.HTTPException):
            pass
        time.sleep(0.25)
    raise Fail(f"{child.name} not healthy before the deadline:\n"
               f"{child.tail()}")


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


def counters(port: int) -> dict[str, float]:
    status, body = http_get(port, "/debug/prometheus_metrics")
    if status != 200:
        raise Fail(f"GET /debug/prometheus_metrics -> {status}")
    return parse_metrics(body.decode())


def cache_entries(path: str) -> frozenset:
    """Names of the compile cache's entries. Names, not a count: the
    cache also drops files, so a count can stand still or fall while
    new executables are written."""
    try:
        return frozenset(e.name for e in os.scandir(path) if e.is_file())
    except OSError:
        return frozenset()


class ChipChild:
    """serve_chip.py and its control channel."""

    def __init__(self, platform: str, logdir: str):
        cmd_r, cmd_w = os.pipe()
        rep_r, rep_w = os.pipe()
        self.started = time.monotonic()
        self.child = Child("alpha-chip", [
            sys.executable, os.path.join(HERE, "serve_chip.py"),
            "--cmd-fd", str(cmd_r), "--reply-fd", str(rep_w)],
            child_env(platform), logdir, pass_fds=(cmd_r, rep_w))
        os.close(cmd_r)
        os.close(rep_w)
        self._cmd = os.fdopen(cmd_w, "w")
        self._replies: queue.Queue = queue.Queue()
        self.port = free_port()
        threading.Thread(target=self._read, args=(os.fdopen(rep_r, "r"),),
                         daemon=True).start()

    def _read(self, f) -> None:
        for line in f:
            try:
                self._replies.put(json.loads(line))
            except ValueError:
                pass
        self._replies.put(None)

    def send(self, **msg) -> None:
        self._cmd.write(json.dumps(msg) + "\n")
        self._cmd.flush()

    def reply(self, timeout: float) -> dict:
        try:
            msg = self._replies.get(timeout=timeout)
        except queue.Empty:
            msg = None
        if msg is None:
            raise Fail(f"{self.child.name} gave no reply on its control "
                       f"channel:\n{self.child.tail()}")
        return msg

    def ask(self, timeout: float, **msg) -> dict:
        self.send(**msg)
        out = self.reply(timeout)
        if not out.get("ok"):
            raise Fail(f"{self.child.name}: {msg} -> {out}")
        return out

    def stop(self) -> None:
        try:
            self._cmd.close()
        except OSError:
            pass
        self.child.stop()


# ----------------------------------------------------------------------
# the graph, the pool, the reference
# ----------------------------------------------------------------------


def build_snapshot(dataset, scale: int, seed: int, variant: str,
                   sdir: str, logdir: str) -> dict:
    """RDF from the dataset module, then the program's bulk loader in
    a CPU child; -> facts (also kept beside the snapshot)."""
    os.makedirs(sdir, exist_ok=True)
    snap = os.path.join(sdir, "p.snap")
    facts_path = os.path.join(sdir, "facts.json")
    if os.path.exists(snap) and os.path.exists(facts_path):
        with open(facts_path) as f:
            return json.load(f) | {"built": False}
    t0 = time.monotonic()
    rdf = os.path.join(sdir, "graph.rdf")
    schema = os.path.join(sdir, "graph.schema")
    with open(rdf, "w") as f:
        facts = dataset.write_rdf(f, scale, seed, variant)
    with open(schema, "w") as f:
        f.write(dataset.SCHEMA)
    facts["generate_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    tmp = snap + ".tmp"
    child = Child(f"bulk{'-' + variant if variant else ''}", [
        sys.executable, "-m", "dgraph_tpu", "bulk", rdf,
        "--schema", schema, "--out", tmp], child_env("cpu"), logdir)
    try:
        child.proc.wait()
    finally:
        child.stop()
    if child.proc.returncode != 0 or not os.path.exists(tmp):
        raise Fail(f"bulk exited {child.proc.returncode}:\n{child.tail()}")
    os.unlink(rdf)
    facts["bulk_s"] = time.monotonic() - t0
    facts["snapshot_bytes"] = os.path.getsize(tmp)
    with open(facts_path, "w") as f:
        json.dump(facts, f)
    os.replace(tmp, snap)
    return facts | {"built": True}


def split_reply(status: int, raw: bytes) -> tuple[bool, str, dict]:
    """(ok, sha256 of `data` as served, server_latency) of one reply.
    ok: HTTP 200, a `data` member, no `errors`. The bytes of `data`
    are hashed as they came: both servers run one encoder, so equal
    answers are equal bytes."""
    if status != 200 or not raw.startswith(b'{"data":'):
        return False, "", {}
    k = raw.rfind(b',"extensions":')
    if k < 0:
        return False, "", {}
    try:
        ext = json.loads(raw[k + len(b',"extensions":'):-1])
    except ValueError:
        return False, "", {}
    data = raw[len(b'{"data":'):k]
    return True, hashlib.sha256(data).hexdigest(), \
        ext.get("server_latency") or {}


class Conn:
    """One keep-alive HTTP connection to an alpha."""

    HEADERS = {"Content-Type": "application/dql"}

    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=900)

    def query(self, body: bytes) -> tuple[int, bytes]:
        self.conn.request("POST", "/query", body=body,
                          headers=self.HEADERS)
        r = self.conn.getresponse()
        return r.status, r.read()

    def close(self) -> None:
        self.conn.close()


def pool_digest(pool: list[dict]) -> str:
    h = hashlib.sha256()
    for e in pool:
        h.update(e["query"].encode())
        h.update(b"\0")
    return h.hexdigest()


def load_reference(path: str, pool: list[dict]) -> list[str] | None:
    """The cached reference hashes, if they are of this very pool."""
    try:
        with open(path) as f:
            lines = [json.loads(ln) for ln in f]
    except (OSError, ValueError):
        return None
    if not lines or lines[0].get("pool_sha256") != pool_digest(pool) \
            or len(lines) != len(pool) + 1:
        return None
    return [ln["sha256"] for ln in lines[1:]]


def record(entry_i: int, t_send: float, t_done: float, status: int,
           raw: bytes, keep: bool = False) -> dict:
    ok, digest, lat = split_reply(status, raw)
    r = {"pool": entry_i, "t_send": t_send, "t_done": t_done,
         "latency_s": t_done - t_send, "status": status, "ok": ok,
         "sha256": digest, "bytes": len(raw), "server": lat}
    if keep and ok:  # the bytes of `data`, for the plain reference
        r["data"] = raw[len(b'{"data":'):raw.rfind(b',"extensions":')]
    return r


def replay(port: int, bodies: list[bytes], clients: int,
           keep: bool = False) -> list[dict]:
    """Every query once, taken in order by `clients` keep-alive
    connections; -> their records, in pool order (`keep`: with the
    bytes of `data`)."""
    out: list = [None] * len(bodies)
    todo = iter(range(len(bodies)))
    lock = threading.Lock()
    errors: list[str] = []

    def work():
        conn = Conn(port)
        try:
            while True:
                with lock:
                    i = next(todo, None)
                if i is None:
                    return
                t0 = time.monotonic()
                status, raw = conn.query(bodies[i])
                out[i] = record(i, t0, time.monotonic(), status, raw, keep)
        except (OSError, http.client.HTTPException) as e:
            errors.append(f"{type(e).__name__}: {e}")
        finally:
            conn.close()

    threads = [threading.Thread(target=work) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise Fail("replaying the pool: " + "; ".join(errors[:3]))
    return out


def make_reference(snap: str, flags: list[str], pool: list[dict],
                   bodies: list[bytes], path: str, logdir: str,
                   deadline: float) -> list[str]:
    """Every pool query answered once by the postings tier alone: an
    `alpha --no-device` child under JAX_PLATFORMS=cpu."""
    port = free_port()
    child = Child("alpha-reference", [
        sys.executable, "-m", "dgraph_tpu", "alpha", "--host",
        "127.0.0.1", "--port", str(port), "--snapshot", snap,
        "--no-device", *flags], child_env("cpu"), logdir)
    try:
        wait_healthy(port, child, deadline)
        answers = replay(port, bodies, 4)
    finally:
        child.stop()
    refused = [f"{pool[r['pool']]['name']}#{pool[r['pool']]['binding']}: "
               f"HTTP {r['status']}" for r in answers if not r["ok"]]
    if refused:
        # traffic is chosen so that no operation fails
        raise Fail("the reference refused: " + "; ".join(refused[:5]))
    hashes = [r["sha256"] for r in answers]
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path + ".tmp", "w") as f:
        f.write(json.dumps({"pool_sha256": pool_digest(pool)}) + "\n")
        for e, h in zip(pool, hashes):
            f.write(json.dumps({"name": e["name"], "binding": e["binding"],
                                "sha256": h}) + "\n")
    os.replace(path + ".tmp", path)
    return hashes


# ----------------------------------------------------------------------
# warm-up and window
# ----------------------------------------------------------------------


def settle(port: int, bodies: list[bytes], clients: int, probe,
           quiet_s: float, give_up: float) -> tuple[list[dict], int, bool]:
    """Passes over the pool, from the mix's clients at once, until the
    program has stopped changing how it answers: `probe()` (compile
    cache entries and the configuration's `settle.counters`) has read
    the same for `quiet_s` seconds. The program's planner tries tiers
    it has no evidence for once a stage has run, a budgeted few at a
    time, and a tier tried for the first time may compile: all of
    that belongs to set-up. -> (replies, passes, settled)."""
    replies: list[dict] = []
    state = probe()
    last_change = time.monotonic()
    passes = 0
    while True:
        replies += replay(port, bodies, clients)
        passes += 1
        now, seen = time.monotonic(), probe()
        if seen != state:
            log(f"settle pass {passes}: the probe moved")
            state, last_change = seen, now
        if now - last_change >= quiet_s:
            return replies, passes, True
        if now >= give_up:
            return replies, passes, False


def run_window(port: int, bodies: list[bytes], seq, clients: int,
               seconds: float, on_start=None) -> dict:
    """The closed loop. Every client sends its next request when its
    last reply is complete, until the window closes; requests in
    flight then are awaited and kept (marked late), so a tail is the
    tail of every request that was sent."""
    lock = threading.Lock()
    state = {"next": 0}
    replies: list[dict] = []
    gaps = [0.0] * clients
    errors: list[str] = []
    barrier = threading.Barrier(clients + 1)
    t = {}

    def client(ci: int):
        conn = Conn(port)
        mine = []
        try:
            barrier.wait()
            t_end = t["end"]
            last_done = None
            while True:
                with lock:
                    i = state["next"]
                    state["next"] = i + 1
                    pi = seq.at(i)
                t_send = time.monotonic()
                if t_send >= t_end:
                    break
                if last_done is not None:
                    gaps[ci] += t_send - last_done
                try:
                    status, raw = conn.query(bodies[pi])
                except (OSError, http.client.HTTPException) as e:
                    status, raw = 0, f"{type(e).__name__}: {e}".encode()
                    conn.close()
                    conn = Conn(port)
                last_done = time.monotonic()
                mine.append(record(pi, t_send, last_done, status, raw))
        except Exception as e:  # noqa: BLE001 -- surfaced after join
            errors.append(f"client {ci}: {type(e).__name__}: {e}")
        finally:
            conn.close()
            with lock:
                replies.extend(mine)

    threads = [threading.Thread(target=client, args=(ci,), daemon=True)
               for ci in range(clients)]
    for th in threads:
        th.start()
    t["start"] = time.monotonic() + 0.05
    t["end"] = t["start"] + seconds
    barrier.wait()
    if on_start:
        on_start(t["start"])
    for th in threads:
        th.join()
    if errors:
        raise Fail("; ".join(errors))
    for r in replies:
        r["in_window"] = r["t_done"] <= t["end"]
    replies.sort(key=lambda r: r["t_send"])
    return {"replies": replies, "t_start": t["start"], "t_end": t["end"],
            "seconds": seconds, "drain_s": max(
                [r["t_done"] for r in replies] + [t["end"]]) - t["end"],
            "client_gap_share": sum(gaps) / (clients * seconds)}


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise Fail(f"BENCHMARK.json has no {what} named {name!r}")


def run(args) -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = find(bench["workloads"], args.workload, "workload")
    cfg_entry = find(bench["configs"], cell["config"], "configuration")
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    if not os.path.isdir(os.path.join(ROOT, "dgraph_tpu")):
        raise Fail("the program (dgraph_tpu/) is not in this checkout")

    rehearse = args.rehearse > 0
    if rehearse:
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
            raise Fail("--rehearse needs JAX_PLATFORMS=cpu set by the "
                       "caller; it is never chosen here")
        platform, scale = "cpu", args.rehearse
        cache_root = tempfile.mkdtemp(prefix="bench_rehearse_")
    else:
        # jax itself raises in the child when there is no such device
        platform, scale = "tpu", int(config["scale"])
        cache_root = os.path.join(HERE, ".cache")
    try:
        return _run(args, bench, cell, config, platform, scale,
                    cache_root, rehearse)
    finally:
        for child in list(Child.live):
            child.stop(grace_s=5.0)
        if rehearse:
            shutil.rmtree(cache_root, ignore_errors=True)


def _run(args, bench, cell, config, platform, scale, cache_root,
         rehearse) -> int:
    dataset = load_module(os.path.join(
        HERE, "datasets", config["dataset"] + ".py"))
    traffic = load_module(os.path.join(HERE, "traffic.py"))
    mix = traffic.load_mix(os.path.join(
        HERE, "traffic", cell["traffic"] + ".json"))
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    deadline = _T0 + GIVE_UP_S
    seed_dir = os.path.join(cache_root, cell["config"], str(args.seed))
    logdir = os.path.join(seed_dir, "logs")
    os.makedirs(logdir, exist_ok=True)

    # -- 1. the chip, first: a missing one fails here, while the graph
    # (host work only) is already being written beside it -------------
    chip = ChipChild(platform, logdir)
    builder = Background("snapshot", build_snapshot, dataset, scale,
                         args.seed, "", seed_dir, logdir)
    device = chip.reply(timeout=180.0)
    if device.get("event") != "device":
        raise Fail(f"chip child said {device} in place of its device")
    if device["count"] < cell["chips"]:
        raise Fail(f"cell {cell['name']} needs {cell['chips']} chips, "
                   f"the child found {device['count']}")
    if not rehearse and device["platform"] == "cpu":
        raise Fail("the chip child runs on the CPU")
    if not rehearse and device["kind"] not in peaks:
        raise Fail(f"device kind {device['kind']!r} is not in peaks.json")
    log(f"chip child holds {device}")

    # -- 2. the graph ---------------------------------------------------
    facts = builder.result()
    served_dir = seed_dir
    if args.control:
        served_dir = f"{seed_dir}-{args.control}"
        build_snapshot(dataset, scale, args.seed, args.control,
                       served_dir, logdir)
    pool = traffic.build_pool(mix, dataset, scale, facts, args.seed)
    bodies = [e["query"].encode() for e in pool]
    seq = traffic.Sequence(pool, len(mix["templates"]), args.seed)
    say(f"graph: scale {scale} seed {args.seed} rdf {facts['rdf']} "
        f"edges {json.dumps(facts['edges'])} "
        f"{'built' if facts['built'] else 'found'} "
        f"(generate {facts.get('generate_s', 0):.1f} s, bulk "
        f"{facts.get('bulk_s', 0):.1f} s, snapshot "
        f"{facts.get('snapshot_bytes', 0)} B); pool {len(pool)} queries "
        f"of {len(mix['templates'])} templates")

    # -- 3. serve; the reference beside it when the cache has none ------
    ref_path = os.path.join(seed_dir, "reference",
                            cell["traffic"] + ".jsonl")
    reference = load_reference(ref_path, pool)
    # alpha's flags are the deployment's: the configuration lists them
    flags = [str(f) for f in config.get("serve_flags", ())]
    ref_thread = None
    if reference is None:
        ref_thread = Background(
            "reference", make_reference, os.path.join(seed_dir, "p.snap"),
            flags, pool, bodies, ref_path, logdir, deadline)
    t_serve = time.monotonic()
    chip.send(cmd="serve", snapshot=os.path.join(served_dir, "p.snap"),
              port=chip.port, flags=flags)
    health = wait_healthy(chip.port, chip.child, deadline)
    snapshot_load_s = time.monotonic() - t_serve
    runtime = health.get("runtime") or {}
    if not runtime.get("native"):
        raise Fail("native C++ runtime unavailable in the chip child: "
                   f"{runtime.get('nativeUnavailableReason')}")
    if (runtime.get("device") or {}).get("platform") != device["platform"]:
        raise Fail(f"/health reports {runtime.get('device')}, the "
                   f"launcher found {device}")
    cache_dir = runtime["compileCache"]
    entries_start = cache_entries(cache_dir)

    # -- 4. warm-up -------------------------------------------------------
    t0 = time.monotonic()
    first_pass, routed = [], {}

    def stages() -> dict[str, float]:
        return {k: v for k, v in counters(chip.port).items()
                if k.startswith(tuple(config.get("device_counters", ())))}

    seen = stages()
    for i, body in enumerate(bodies):
        # one at a time, so the counters say which stages of THIS
        # query went to the device
        first_pass += replay(chip.port, [body], 1, keep=True)
        first_pass[-1]["pool"] = i
        now = stages()
        routed.setdefault(pool[i]["name"], set()).update(
            k for k, v in now.items() if v != seen.get(k, 0))
        seen = now
    warm = list(first_pass)
    first_pass_s = time.monotonic() - t0
    say("first pass, device stages by template: " + "; ".join(
        f"{name}: {', '.join(sorted(ks)) or 'NONE (host tiers only)'}"
        for name, ks in routed.items()))
    rule = config.get("settle", {})

    def probe():
        c = counters(chip.port)
        return (cache_entries(cache_dir),) + tuple(
            sum(v for k, v in c.items() if k.startswith(name))
            for name in rule.get("counters", ()))

    more, passes, settled = settle(
        chip.port, bodies, int(mix["clients"]), probe,
        float(rule.get("quiet_s", 0.0)),
        min(deadline, time.monotonic() + float(rule.get("max_s", 0.0))))
    warm += more
    warmup_s = time.monotonic() - t0
    entries_warm = cache_entries(cache_dir)
    t_ready = time.monotonic()
    if ref_thread is not None:
        reference = ref_thread.result(
            timeout=max(1.0, deadline - time.monotonic()))
    # waiting for the reference is the benchmark's cost, not the
    # system's: it is not counted as set-up
    reference_wait_s = time.monotonic() - t_ready
    before = counters(chip.port)
    setup_s = time.monotonic() - _T0 - reference_wait_s

    # -- 5. the window ----------------------------------------------------
    trace_dir = os.path.join(seed_dir, "trace")

    def take_trace(t_start: float) -> tuple[float, float]:
        """A device trace of a slice of the window; -> its interval on
        this process's clock."""
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = min(TRACE_MAX_S, TRACE_SHARE * args.seconds)
        time.sleep(max(0.0, t_start + TRACE_FROM * args.seconds
                       - time.monotonic()))
        t_a = time.monotonic()
        chip.ask(60.0, cmd="trace_start", dir=trace_dir)
        time.sleep(max(0.0, t_a + span - time.monotonic()))
        t_b = time.monotonic()
        chip.ask(600.0, cmd="trace_stop")
        return t_a, t_b

    tracers: list[Background] = []
    # the load generator's own collector stays out of the window
    gc.collect()
    gc.disable()
    window = run_window(
        chip.port, bodies, seq, int(mix["clients"]), args.seconds,
        (lambda t: tracers.append(Background("device trace", take_trace, t)))
        if args.trace else None)
    gc.enable()
    # the window and its drain on the wall clock, which the launcher's
    # collector watch uses
    to_wall = time.time() - time.monotonic()
    wall_start = window["t_start"] + to_wall
    wall_end = window["t_end"] + window["drain_s"] + to_wall
    traced = tracers[0].result(timeout=900.0) if tracers else None
    after = counters(chip.port)
    full_gc = chip.ask(60.0, cmd="gc")["full_collections"]
    entries_end = cache_entries(cache_dir)
    memory = chip.ask(60.0, cmd="memory")["peak_bytes"]
    chip.stop()

    # -- 6. reduce, compare, report ---------------------------------------
    trace = None
    if args.trace:
        out_path = os.path.join(seed_dir, "trace.json")
        red = subprocess.run(
            [sys.executable, os.path.join(HERE, "trace_reduce.py"),
             trace_dir, "--out", out_path],
            env=child_env("cpu"), cwd=ROOT, capture_output=True,
            text=True, timeout=600)
        if red.returncode != 0:
            raise Fail(f"trace_reduce.py failed:\n{red.stderr[-2000:]}")
        with open(out_path) as f:
            trace = json.load(f)
        trace["t_a"], trace["t_b"] = traced
        shutil.rmtree(trace_dir, ignore_errors=True)

    replies = window["replies"]
    for r in warm + replies:
        r["good"] = r["ok"] and r["sha256"] == reference[r["pool"]]
    bad_warm = [r for r in warm if not r["good"]]
    bad = [r for r in replies if not r["good"]]
    compiles = len(entries_end - entries_warm)
    # the plain reference (it imports nothing of the program): the
    # first pass's answers as objects; every later reply is held to
    # the same bytes through the hashes above
    plain_differ, plain_have = [], 0
    if config.get("plain_reference"):
        plain = load_module(os.path.join(
            HERE, "datasets", config["plain_reference"] + ".py"))
        for r in first_pass:
            e = pool[r["pool"]]
            answer = plain.ANSWERS.get(e["name"])
            if answer is None:
                continue
            plain_have += 1
            want = answer(dataset, scale, facts, e["query"])
            if not r["ok"] or json.loads(r["data"]) != want:
                plain_differ.append(r)
                log(f"PLAIN REFERENCE DIFFERS {e['name']}#{e['binding']}: "
                    f"{r.get('data', b'')[:200]!r} != "
                    f"{json.dumps(want)[:200]}")
    for r in (bad_warm + bad)[:8]:
        e = pool[r["pool"]]
        log(f"MISMATCH {e['name']}#{e['binding']}: HTTP {r['status']} "
            f"ok={r['ok']} {r['sha256'][:12]} != "
            f"{reference[r['pool']][:12]}")
    # each number compared, beside its limit: the result's last key and
    # the run's last lines on stderr, which is what the driver's record
    # keeps of a run that is not correct
    compared = {
        "window_replies_mismatching": {
            "value": len(bad), "limit": 0, "of": len(replies)},
        "warmup_replies_mismatching": {
            "value": len(bad_warm), "limit": 0, "of": len(warm)},
        "compile_cache_entries_added_in_window": {
            "value": compiles, "limit": 0},
        "plain_answers_differing": {
            "value": len(plain_differ), "limit": 0, "of": plain_have},
    }
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    say(f"compared: window replies mismatching the reference "
        f"{len(bad)} of {len(replies)} (limit 0); warm-up replies "
        f"mismatching {len(bad_warm)} of {len(warm)} (limit 0); "
        f"compile-cache entries added in the window {compiles} "
        f"(limit 0); first-pass answers differing from the plain "
        f"reference {len(plain_differ)} of the {plain_have} pool queries "
        f"(of {len(pool)}) it answers (limit 0)")
    in_window = [r for r in replies if r["in_window"]]
    say(f"phases: chip taken {chip.started - _T0:.1f} s after start; "
        f"snapshot load {snapshot_load_s:.1f} s; warm-up "
        f"{warmup_s:.1f} s (first pass {first_pass_s:.1f} s, then "
        f"{passes} passes by the mix's clients until "
        f"{', '.join(['compile-cache entries', *rule.get('counters', ())])} "
        f"stood still for "
        f"{rule.get('quiet_s', 0)} s: "
        f"{'settled' if settled else 'NOT SETTLED, gave up'}); waited for the reference "
        f"{reference_wait_s:.1f} s (not in set-up); compile cache "
        f"{cache_dir} entries {len(entries_start)} at start, "
        f"+{len(entries_warm - entries_start)} in warm-up, "
        f"+{compiles} in the window")
    say(f"window: {args.seconds} s, {len(replies)} requests sent, "
        f"{len(in_window)} replies inside it, drain "
        f"{window['drain_s']:.2f} s; client threads spent "
        f"{100 * window['client_gap_share']:.2f}% of the window between "
        f"a reply's last byte and the next request's first"
        + ("" if len(in_window) >= 200 else
           "; FEWER THAN 200 REPLIES: the 95th percentile rests on "
           f"{len(in_window)}"))
    by_t: dict[str, list[float]] = {}
    for r in replies:
        by_t.setdefault(pool[r["pool"]]["name"], []).append(
            r["latency_s"] * 1e3)
    slow = sorted(by_t.items(), key=lambda kv: -sum(kv[1]))[:8]
    total = sum(sum(v) for v in by_t.values())
    say("templates by client time in the window (name: requests, "
        "median ms, share of all client time): " + "; ".join(
            f"{n}: {len(v)}, {sorted(v)[len(v) // 2]:.0f}, "
            f"{100 * sum(v) / total:.1f}%" for n, v in slow))
    in_gc = [d for t, d in full_gc if wall_start <= t + d and t <= wall_end]
    say(f"server's collector: {len(full_gc)} full collections since it "
        f"started, {len(in_gc)} of them in the window, "
        f"{sum(in_gc):.2f} s together, the longest "
        f"{max(in_gc, default=0.0):.2f} s (each stops every request "
        "thread)")
    say(f"gate: device_dispatch_seconds "
        f"{after.get('device_dispatch_seconds')}; in the window: "
        + ", ".join(
            f"{name} +{sum(v - before.get(k, 0) for k, v in after.items() if k.startswith(name)):.0f}"
            for name in config.get("report_counters", ())))

    ctx = {
        "cell": cell, "config": config, "scale": scale, "facts": facts,
        "pool": pool, "mix": mix, "replies": replies, "warm": warm,
        "window_s": float(args.seconds), "setup_s": setup_s,
        "snapshot_load_s": snapshot_load_s, "warmup_s": warmup_s,
        "counters_before": before, "counters_after": after,
        "compiles_in_window": compiles, "trace": trace,
        "peaks": peaks.get(device["kind"]), "device": device,
        "bench_dir": HERE, "stats": load_module(os.path.join(HERE, "stats.py")),
        "load_module": load_module, "dataset": dataset, "notes": [],
    }
    def read_section(section: str) -> dict:
        out = {}
        for m in bench[section]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            if rehearse and m["source"] != "program_counter":
                continue  # a CPU run gives no time, rate or share
            reader = load_module(os.path.join(HERE, "metrics",
                                              m["name"] + ".py"))
            value = reader.read(ctx)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    metrics = read_section("per_layer" if args.trace else "end_to_end")
    if args.trace and not rehearse:
        # against the same seed's --trace 0 run this is the tracer's cost
        say("with the tracer on, end to end: " + json.dumps(
            read_section("end_to_end")))

    for note in ctx["notes"]:
        say(note)
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": max(
               (b for b in memory if b is not None), default=None)}
    result = {"correct": correct, "attempted": len(replies),
              "failed": len(bad), "metrics": metrics, "device": dev}
    if trace is not None and trace.get("busy_s") is not None:
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"][:10],
                               "idle_gaps": trace["idle_gaps"][:10]}
        say("device programs by time: "
            + json.dumps(trace["programs"][:10]))
    result["compared"] = compared
    for line in _SAID:
        print(line)
    print(json.dumps(result), flush=True)
    for name, c in compared.items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})"
              + (f" of {c['of']}" if "of" in c else ""),
              file=sys.stderr, flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="SCALE",
                    help="CPU walk-through at this scale; needs "
                         "JAX_PLATFORMS=cpu in the environment")
    ap.add_argument("--control", default="",
                    help="serve this degraded variant of the graph "
                         "against the sound reference")
    args = ap.parse_args(argv)

    def _term(signum, _frame):
        raise Fail(f"signal {signum}")

    signal.signal(signal.SIGTERM, _term)
    try:
        return run(args)
    except Fail as e:
        log("FAILED: " + str(e))
        return 1


if __name__ == "__main__":
    sys.exit(main())
