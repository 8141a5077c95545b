"""Read scale-out smoke (~30 s): learner replicas + result cache + QoS.

The CI gate over the read scale-out serving tier (tools/check.sh):

Part 1 — embedded result cache, byte parity under churn:
  1. a GraphDB with --result-cache on answers a repeated best-effort
     query from cache with the EXACT bytes the first execution
     produced (query_json string identity);
  2. interleaved writes to the query's predicate footprint invalidate
     via the CDC observer: every post-write read's DATA payload is
     byte-identical to an uncached oracle (the same engine with the
     cache momentarily detached);
  3. writes OUTSIDE the footprint leave the entry cached (hits keep
     counting).

Part 2 — live cluster: 1 voter + 1 learner, cache + tenant QoS armed:
  4. the learner conf-joins as a NON-VOTING member and serves a
     watermark-bounded read at a zero-granted read_ts with the same
     data bytes as the voter at the SAME read_ts (replica parity);
  5. routed best-effort reads keep observing fresh writes (a read_ts
     granted after a commit can never see state older than it);
  6. bounded staleness under a partition: the learner is SIGSTOPped
     (a network-indistinguishable partition) while acked writes keep
     advancing a counter, then SIGCONTed and read directly at fresh
     zero grants. Every read it SERVES observes a counter >= the last
     write acked before its grant; StaleRead / unreachable are
     acceptable refusals, an older counter is a violation;
  7. tenant QoS isolation: a hot tenant flooding reads degrades to
     typed sheds (Overloaded -> the 429 class) while a quiet tenant's
     trickle completes with ZERO errors.

Exit 0 = pass. Wired into tools/check.sh.
"""

from __future__ import annotations

import json
import signal
import sys
import threading
import time


def log(msg: str):
    print(f"[scaleout-smoke] {msg}", file=sys.stderr, flush=True)


def _data(body: str) -> str:
    """Canonical DATA payload of a query_json body (extensions carry
    per-execution timings, so parity is over data)."""
    return json.dumps(json.loads(body).get("data"), sort_keys=True)


def part1_embedded() -> dict:
    from dgraph_tpu.engine.db import GraphDB

    db = GraphDB(prefer_device=False, result_cache_entries=128)
    db.alter("so.name: string @index(exact) .\n"
             "so.other: string .")
    for i in range(4):
        db.mutate(set_nquads=f'<{hex(0x100 + i)}> <so.name> "n{i}" .')
    q = '{ q(func: has(so.name)) { so.name } }'

    def uncached() -> str:
        rc, db.result_cache = db.result_cache, None
        try:
            return db.query_json(q, best_effort=True)
        finally:
            db.result_cache = rc

    # 1: fill then hit — the hit is the fill's exact bytes
    b1 = db.query_json(q, best_effort=True)
    b2 = db.query_json(q, best_effort=True)
    assert b1 == b2, "cached hit diverged from its own fill"
    st = db.result_cache.stats()
    assert st["hits"] >= 1 and st["entries"] >= 1, st
    log(f"embedded fill+hit ok ({st['entries']} entries)")

    # 2: churn on the footprint — every post-write read matches the
    # uncached oracle byte-for-byte on data
    for i in range(5):
        db.mutate(set_nquads=f'<{hex(0x200 + i)}> <so.name> "c{i}" .')
        got = db.query_json(q, best_effort=True)
        want = uncached()
        assert _data(got) == _data(want), \
            f"churn round {i}: cached read diverged from oracle"
        assert f"c{i}" in got, f"round {i}: invalidation missed"
    inv = db.result_cache.stats()["invalidations"]
    assert inv >= 5, f"expected >=5 invalidations, saw {inv}"
    log(f"churn parity ok ({inv} invalidations)")

    # 3: a write OUTSIDE the footprint must NOT invalidate
    before = db.query_json(q, best_effort=True)  # re-fill
    h0 = db.result_cache.stats()["hits"]
    db.mutate(set_nquads='<0x999> <so.other> "noise" .')
    after = db.query_json(q, best_effort=True)
    assert after == before, "unrelated write evicted the entry"
    assert db.result_cache.stats()["hits"] == h0 + 1, \
        "unrelated write caused a miss"
    log("footprint isolation ok")
    return {"invalidations": inv}


def bounded_staleness(pc, rc, laddr, lname: str) -> dict:
    """Step 6: no read the learner serves is older than its grant."""
    from dgraph_tpu.cluster.client import ClusterClient
    from dgraph_tpu.cluster.errors import StaleRead

    stop_s = 2.0
    lcl = ClusterClient({1: laddr}, timeout=3.0)
    state = {"acked": 0, "stop": False}
    wlock = threading.Lock()

    def writer():
        # paced: the learner must be able to out-apply the stream or
        # recovery never converges — the bound under test is
        # staleness, not apply bandwidth
        i = 0
        while not state["stop"]:
            i += 1
            try:
                rc.mutate(set_nquads=f'<0x77> <so.ctr> "{i}" .')
            except Exception:  # noqa: BLE001 — keep writing  # dglint: disable=DG07 (nemesis load loop: a refused write just retries next tick)
                continue
            with wlock:
                state["acked"] = i
            time.sleep(0.1)

    tallies = {"ok": 0, "refused": 0, "violation": 0}

    def read_learner():
        # the acked floor is captured BEFORE the grant, so every
        # served value must be >= it
        with wlock:
            floor = state["acked"]
        ts = rc.zero.read_ts()
        try:
            out = lcl.query_at(1, '{ q(func: uid(0x77)) { so.ctr } }',
                               read_ts=ts, deadline_ms=2500)
        except (StaleRead, ConnectionError, OSError):
            tallies["refused"] += 1
            return
        rows = (out.get("data") or {}).get("q") or []
        v = int(rows[0].get("so.ctr", 0)) if rows else 0
        tallies["violation" if v < floor else "ok"] += 1

    wt = threading.Thread(target=writer, daemon=True)
    wt.start()
    try:
        end = time.monotonic() + 2.0
        while time.monotonic() < end:   # healthy: the learner serves
            read_learner()
            time.sleep(0.05)
        healthy_ok = tallies["ok"]
        pc.kill(lname, signal.SIGSTOP)
        try:
            end = time.monotonic() + stop_s
            while time.monotonic() < end:
                read_learner()          # refuses, never serves old
        finally:
            pc.kill(lname, signal.SIGCONT)
        # catch-up must finish BEFORE the learner answers again
        resumed_ok = 0
        end = time.monotonic() + 30.0
        while time.monotonic() < end and resumed_ok < 8:
            before = tallies["ok"]
            read_learner()
            resumed_ok += tallies["ok"] - before
            time.sleep(0.05)
    finally:
        state["stop"] = True
        wt.join(timeout=5.0)
        lcl.close()
    assert tallies["violation"] == 0, f"stale read served: {tallies}"
    assert healthy_ok >= 3 and resumed_ok >= 8, \
        f"learner stopped serving: {tallies}, resumed {resumed_ok}"
    log(f"bounded staleness ok across a {stop_s}s partition: "
        f"{tallies}, {state['acked']} acked writes")
    return tallies


def part2_cluster() -> dict:
    from dgraph_tpu.bench.spawn import ProcessCluster
    from dgraph_tpu.cluster.client import ClusterClient
    from dgraph_tpu.utils.reqctx import Overloaded

    with ProcessCluster(
            groups=1, replicas=1, learners=1, zeros=1,
            alpha_args=["--result-cache", "512",
                        "--tenant-rate", "50",
                        "--tenant-burst", "25"]) as pc:
        pc.wait_ready()
        pc.wait_learners()
        log("1 voter + 1 learner up; learner conf-joined")
        rc = pc.routed()
        try:
            rc.alter("so.name: string @index(exact) .\n"
                     "so.ctr: int .")
            for i in range(8):
                rc.mutate(set_nquads=f'<{hex(0x100 + i)}> <so.name> '
                          f'"n{i}" .')
                time.sleep(0.02)  # stay inside the tenant bucket
            q = '{ q(func: has(so.name)) { so.name } }'

            # 4: voter and learner serve the SAME bytes at one read_ts
            ts = rc.zero.read_ts()
            vaddr = pc.group_addrs[1][1]
            laddr = pc.learner_addrs[1][2]
            cl = ClusterClient({1: vaddr, 2: laddr}, timeout=30.0)
            try:
                deadline = time.monotonic() + 30.0
                while True:
                    try:
                        got_v = cl.query_at(1, q, read_ts=ts,
                                            deadline_ms=10_000)
                        got_l = cl.query_at(2, q, read_ts=ts,
                                            deadline_ms=10_000)
                        break
                    except Exception as e:  # noqa: BLE001 — StaleRead
                        if time.monotonic() > deadline:
                            raise
                        log(f"replica read retry: {e}")
                        time.sleep(0.3)
                dv = json.dumps(got_v.get("data"), sort_keys=True)
                dl = json.dumps(got_l.get("data"), sort_keys=True)
                assert dv == dl, \
                    f"replica divergence at ts {ts}:\n {dv}\n {dl}"
                assert '"n7"' in dv, dv
            finally:
                cl.close()
            log(f"voter/learner byte parity at read_ts {ts} ok")

            # 5: a granted read_ts after a commit always sees it
            for i in range(3):
                rc.mutate(set_nquads=f'<{hex(0x300 + i)}> <so.name> '
                          f'"f{i}" .')
                time.sleep(0.06)  # roll past the read_ts-grant window
                got = rc.query(q, best_effort=True, tenant="smoke")
                body = json.dumps(got.get("data"), sort_keys=True)
                assert f"f{i}" in body, \
                    f"best-effort read missed committed f{i}"
            log("routed best-effort reads observe fresh commits")

            # 6: a partitioned learner refuses, it never serves old
            bounded_staleness(pc, rc, laddr, "alpha-g1-n2")

            # 7: tenant shed isolation — the hog sheds, quiet doesn't
            sheds = served = 0
            for _ in range(60):
                try:
                    rc.query(q, best_effort=True, tenant="hog")
                    served += 1
                except Overloaded:
                    sheds += 1
            quiet_errors = 0
            for _ in range(5):
                time.sleep(0.05)
                try:
                    rc.query(q, best_effort=True, tenant="quiet")
                except Overloaded:
                    quiet_errors += 1
            assert sheds > 0, \
                f"hog tenant never shed ({served} served)"
            assert quiet_errors == 0, \
                f"quiet tenant shed {quiet_errors}x behind the hog"
            log(f"tenant isolation ok (hog: {sheds} sheds / "
                f"{served} served; quiet: 0 errors)")
            return {"sheds": sheds, "read_ts": ts}
        finally:
            rc.close()


def main() -> int:
    t0 = time.monotonic()
    r1 = part1_embedded()
    r2 = part2_cluster()
    print(json.dumps({"scaleout_smoke": "ok", **r1, **r2,
                      "seconds": round(time.monotonic() - t0, 1)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
