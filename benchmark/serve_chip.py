#!/usr/bin/env python3
"""Launcher of the chip child: the program's own `alpha`, plus a
control channel the benchmark needs and the program does not serve.

The harness's parent never touches jax, and only the process that
holds the chip can trace it or read its memory. So the parent starts
this file in place of `python -m dgraph_tpu alpha`. It

  1. takes the chip (`jax.devices()` under the parent's
     JAX_PLATFORMS, so jax itself raises when there is none) and says
     what it found on the reply pipe: a missing chip fails the run in
     seconds, before any graph is built;
  2. waits for {"cmd": "serve", "snapshot": ..., "port": ...,
     "flags": [...]} on the command pipe (the parent may still be
     building the snapshot);
  3. calls dgraph_tpu.cli.main(["alpha", "--snapshot", ..., *flags]):
     the entry point a user calls, in the main thread, with the flags
     the configuration's file lists (none: alpha's defaults);
  4. meanwhile answers, on a thread of its own:
       {"cmd": "trace_start", "dir": d}  jax.profiler.start_trace(d)
       {"cmd": "trace_stop"}             jax.profiler.stop_trace()
       {"cmd": "memory"}                 peak bytes in use per device
       {"cmd": "gc"}                     the interpreter's full
                                         collections so far: [start on
                                         time.time(), seconds] each

Nothing of the program is changed or configured here; the collector is
only watched (gc.callbacks), because a full collection stops every
request thread at once. When the program serves a way to take a device
trace and to read device memory, this file can go (PERF.md, list for
the tracing issue).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time


_FULL_COLLECTIONS: list[list[float]] = []


def _watch_gc(phase: str, info: dict) -> None:
    if info["generation"] != 2:
        return
    if phase == "start":
        _FULL_COLLECTIONS.append([time.time(), 0.0])
    elif _FULL_COLLECTIONS:
        last = _FULL_COLLECTIONS[-1]
        last[1] = time.time() - last[0]


def _control(cmd_f, reply) -> None:
    import jax

    for line in cmd_f:
        try:
            msg = json.loads(line)
            cmd = msg.get("cmd")
            if cmd == "trace_start":
                # no Python tracer: it would record every call of the
                # server's eight request threads
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(msg["dir"], profiler_options=opts)
                reply({"ok": True, "cmd": cmd, "t": time.time()})
            elif cmd == "trace_stop":
                t = time.time()
                jax.profiler.stop_trace()
                reply({"ok": True, "cmd": cmd, "t": t,
                       "export_s": time.time() - t})
            elif cmd == "memory":
                peaks = []
                for d in jax.local_devices():
                    stats = d.memory_stats() or {}
                    peaks.append(stats.get("peak_bytes_in_use"))
                reply({"ok": True, "cmd": cmd, "peak_bytes": peaks})
            elif cmd == "gc":
                reply({"ok": True, "cmd": cmd,
                       "full_collections": list(_FULL_COLLECTIONS)})
            else:
                reply({"ok": False, "cmd": cmd, "error": "unknown command"})
        except Exception as e:  # noqa: BLE001 -- reported to the parent
            reply({"ok": False, "error": f"{type(e).__name__}: {e}"})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cmd-fd", type=int, required=True)
    ap.add_argument("--reply-fd", type=int, required=True)
    args = ap.parse_args(argv)
    cmd_f = os.fdopen(args.cmd_fd, "r")
    reply_f = os.fdopen(args.reply_fd, "w")
    lock = threading.Lock()

    def reply(obj: dict) -> None:
        with lock:
            reply_f.write(json.dumps(obj) + "\n")
            reply_f.flush()

    import jax

    devs = jax.devices()
    reply({"event": "device", "platform": devs[0].platform,
           "kind": devs[0].device_kind, "count": len(devs)})
    line = cmd_f.readline()
    if not line:
        return 1  # the parent went away before it had a snapshot
    msg = json.loads(line)
    if msg.get("cmd") != "serve":
        reply({"ok": False, "error": f"expected serve, got {msg!r}"})
        return 1
    threading.Thread(target=_control, args=(cmd_f, reply),
                     daemon=True).start()
    gc.callbacks.append(_watch_gc)

    from dgraph_tpu.cli import main as cli_main

    return cli_main(["alpha", "--host", "127.0.0.1",
                     "--port", str(msg["port"]),
                     "--snapshot", msg["snapshot"],
                     *msg.get("flags", ())])


if __name__ == "__main__":
    sys.exit(main())
