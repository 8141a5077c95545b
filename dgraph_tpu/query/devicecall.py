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

and, INSIDE `wait`, for a block that rode a `Rendezvous`' call:

  queue    joining -> its call's launch: the stand behind the call in
           flight (`Ride.waited_ns`; 0 where it found the chip free or
           filled a call that went behind the one in flight: the rest
           of that call is then in `wait` beyond `queue`).

Every mark is a wall-clock read: the thread's CPU clock
(`time.thread_time_ns`) is a system call that holds the interpreter,
15-30 us under load on the chip's host (PERF.md section 6, PR 39):
no request reads it, a scrape does (`metrics.watch_thread_cpu`).
What a plain dispatch found at the chip is counted, not timed:
`ahead`, the device calls of this process dispatched and not yet
ready when it began to wait (a rendezvous' call counts as one, its
riders as none).

A block that dispatched (called `wait`) and raised nothing writes, at
its exit: the site's own counter (names and labels as they always
were: `query_device_*_total`, `query_fused_dispatch_total`,
`query_sharded_expand_total`); a `device.call` span with `family`,
`program`, `enqueue_us`, `wait_us`, `fetch_us`, `out_bytes`, and
`ahead` (a plain dispatch) or `flight` (a rider: the id of the
`device.flight` span its result came from); the request's roll-up
(`sink`: engine/db.py's Latency, or None outside a request: the three
phases and `device_queue_ns`); `device_call_ns_total{family,phase}`
(the three phases: readers sum them), a rider's
`device_call_queue_ns_total{family}` and a plain dispatch's
`device_call_ahead_total{family}`. A block that never dispatched (the
callee declined: >32-bit uids, an empty frontier) counts nothing, as
before. Each phase is also a `jax.profiler.TraceAnnotation`
(`device.enqueue`, `device.wait`, `device.fetch`), so an idle gap of a
device profile reads as host dispatch overhead, queueing or transfer.

Callees that dispatch and fetch in one function (`expand_np`,
`setops.union_many_device`, `bitgraph.sssp_dist`) take `sync=dc.wait`:
a function applied to the dispatched result before it is fetched.

Where the requests in flight can share ONE call of a program, each
still runs its own block and meets the others at a `Rendezvous`: its
`wait` is then `dc.wait_for(...)`, from joining until its call's
result is in, queueing behind the call in flight included, exactly as
queueing behind other requests' programs is above. Three families do:
the k-hop traversal's lanes (`recurse`), the one-path `shortest`'s
pairs (`shortest`) and the exact `similar_to`'s query rows over a
resident vector block (`similar`, one rendezvous a metric). The call
itself is spanned once, by the thread that lands it: `device.flight`,
with the phases of the turn-round between two calls (`Rendezvous`),
and counted once, for every family alike:
`rendezvous_calls_total{family}` and `rendezvous_riders_total{family}`
(riders a call is their ratio).
"""

from __future__ import annotations

import contextlib
import threading
import time

from dgraph_tpu.utils.metrics import inc_counter, watch_gauge
from dgraph_tpu.utils.tracing import span, trace_annotation


def _family(counter: str) -> str:
    """`query_device_sort_page_total` -> `sort_page`,
    `query_fused_dispatch_total` -> `fused_dispatch`."""
    name = counter.removeprefix("query_").removesuffix("_total")
    return name.removeprefix("device_")


_NO_ANNOTATION = contextlib.nullcontext()


def _annotation(name: str):
    """`name` on the profiler's host plane for a `with` block; nothing
    in a process that never imported jax."""
    return trace_annotation(name) or _NO_ANNOTATION


# the device calls of this process dispatched and not yet ready: a
# plain dispatch from `wait` until the device has its result, a
# rendezvous' call from its launch until `land` returns; published as
# a gauge at a scrape, not at every move
_inflight = 0
_inflight_lock = threading.Lock()
watch_gauge("device_calls_inflight", lambda: _inflight)


def _dispatched(n: int) -> int:
    """Move the count of calls on the device by `n`; -> what it was."""
    global _inflight
    with _inflight_lock:
        was = _inflight
        _inflight = was + n
    return was


class device_call:
    # dglint: guarded-by=*:single-thread (one block per dispatch of one
    # request: the thread that enters it leaves it)
    __slots__ = ("_sink", "_counter", "_labels", "_span", "_attrs",
                 "_ann", "_t0", "_t1", "_t2", "_out_bytes", "_ahead",
                 "_queue_ns")

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
        self._ann = self._ahead = self._queue_ns = None
        self._t1 = self._t2 = 0
        self._phase("device.enqueue")
        self._t0 = time.perf_counter_ns()
        return self

    def wait(self, out):
        """The dispatched result, once the device has produced it."""
        import jax

        self._ahead = _dispatched(+1)
        try:
            ready = self.wait_for(lambda: jax.block_until_ready(out))
        finally:
            _dispatched(-1)
        nbytes = getattr(ready, "nbytes", None)
        self._out_bytes = int(nbytes) if nbytes is not None else sum(
            int(x.nbytes) for x in jax.tree_util.tree_leaves(ready))
        return ready

    def wait_for(self, ready, out_bytes: int = 0):
        """`ready()`'s result, for a block whose dispatch is not a
        device array yet: `ready` returns once the device has produced
        what the block waits for (a seat in a call that a Rendezvous
        dispatches: the `Ride` it returns says how long the block
        stood, and which flight brought its result). `out_bytes`: what
        the block takes off the device of it."""
        t1 = time.perf_counter_ns()
        self._phase("device.wait")
        out = ready()
        t2 = time.perf_counter_ns()
        self._phase("device.fetch")
        self._t1, self._t2 = t1, t2
        self._out_bytes = out_bytes
        if isinstance(out, Ride):
            self._queue_ns = out.waited_ns
            self._attrs["flight"] = out.flight.span_id
        return out

    def note(self, **attrs) -> None:
        """More attributes of the block's `device.call` span."""
        self._attrs.update(attrs)

    def __exit__(self, etype, exc, tb) -> None:
        t3 = time.perf_counter_ns()
        self._phase(None)
        if self._t2 and etype is None:
            inc_counter(self._counter, labels=self._labels)
            phases = (("enqueue", self._t1 - self._t0),
                      ("wait", self._t2 - self._t1),
                      ("fetch", t3 - self._t2))
            a = self._attrs
            family = a["family"]
            for phase, ns in phases:
                a[phase + "_us"] = ns // 1000
                inc_counter("device_call_ns_total", ns,
                            labels={"family": family, "phase": phase})
            queue_ns = self._queue_ns
            if queue_ns is not None:
                # a series of its own: the stand lies INSIDE `wait`,
                # and readers sum device_call_ns_total's phases
                inc_counter("device_call_queue_ns_total", queue_ns,
                            labels={"family": family})
            if self._ahead is not None:
                a["ahead"] = self._ahead
                inc_counter("device_call_ahead_total", self._ahead,
                            labels={"family": family})
            a["out_bytes"] = self._out_bytes
            sink = self._sink
            if sink is not None:
                sink.device_calls += 1
                sink.device_enqueue_ns += phases[0][1]
                sink.device_wait_ns += phases[1][1]
                sink.device_fetch_ns += phases[2][1]
                sink.device_queue_ns += queue_ns or 0
        self._span.__exit__(etype, exc, tb)


class _Flight:
    """One call of a Rendezvous: its riders in lane order, what
    `launch` handed back, who blocks for the result, and whether it
    went onto the device's queue behind a call still in flight."""

    __slots__ = ("riders", "handle", "launched", "lander", "t_launch",
                 "span_id", "ahead")

    def __init__(self, riders: list, ahead: bool):
        self.riders = riders
        self.handle = None
        self.launched = False
        self.lander = None
        self.t_launch = 0
        self.span_id = ""       # of its `device.flight` span
        self.ahead = ahead


class Ride:
    """A caller's seat: `result` is its own element of what `land`
    returned, `lane` its place in the call, `lanes` how many rode the
    call, `waited_ns` how long it stood before its call was launched
    (0 for a caller that found the chip free, and for the one whose
    arrival filled a call)."""

    __slots__ = ("item", "flight", "done", "result", "error", "lane",
                 "t_join", "stood")

    def __init__(self, item):
        self.item = item
        self.flight = None
        self.done = False
        self.result = self.error = None
        self.lane = 0
        self.stood = False
        self.t_join = time.perf_counter_ns()

    @property
    def lanes(self) -> int:
        return len(self.flight.riders)

    @property
    def waited_ns(self) -> int:
        return max(0, self.flight.t_launch - self.t_join) \
            if self.stood else 0


class Rendezvous:
    """Where the callers of one device program over ONE resident tile
    meet, so that those in flight ride one call of it.

    A caller that finds no call of the tile in flight launches at
    once, with whoever waits with it: alone, if alone, and then it
    never waits. One that finds a call in flight waits. A call is
    closed by who waits when the chip comes free, or at once when
    full, never by a timer: there is no window and no knob.

    *When the chip comes free:* the thread that took a call's result
    launches ALL the waiters (up to `capacity`; the rest form the next
    call) before it hands anything out, so the chip never stands idle
    for a thread to wake, and one of the new call's riders then blocks
    for its result.

    *At once when full:* a call of `capacity` riders can gain none by
    waiting, so while a call is in flight and none stands behind it,
    the rider whose arrival fills the waiters' call launches it there
    and then, onto the device's queue BEHIND the call in flight, and
    blocks for its result as a lone caller does. The device runs its
    programs in order: the second starts when the first ends, with no
    host thread in between. At most ONE call stands behind the one in
    flight (the chip is never idle with one behind, and a deeper queue
    would only lengthen what a cancelled rider's dropped lane costs);
    a landing that moves it up puts a full set of waiters, if there is
    one, behind it in turn, and boards nobody otherwise. Launches
    never overlap (a call goes behind one that `launch` has returned
    for), so the device's order is the order of boarding, on every
    chip of a mesh.

    `launch(items) -> handle` puts one call for `items` (the riders'
    own, in lane order) on the device and returns without waiting;
    `land(handle, n) -> [n results]` returns once the device has
    produced them. Every caller passes the same two. One that raises
    fails the riders of ITS call with that error, each in its own
    thread, and takes that call off the chip's queue: the call ahead
    of it or behind it, its lander and the waiters are untouched, and
    none waits for ever. A rider whose context is cancelled or past
    its deadline while it waits leaves with its own error; its lane,
    if its call is boarded already, is computed and dropped.

    Kept on the tile it serves (`Rendezvous.at`), so requests meet
    only over the very object their own read_ts resolved to: another
    base_ts, direction or predicate is another tile and another
    rendezvous. And one a FAMILY: the k-hop traversals (`recurse`)
    and the shortest paths (`shortest`) over one tile are two
    programs, so each family has its own calls, queue and counters,
    and a rider never boards the other's. (And one a `key` within a
    family where its calls cannot share a program: the vector scan's
    metric.)

    The thread that lands a call spans it: one `device.flight` span a
    call, with `family`, `lanes`, `ahead` (it was launched behind a
    call still in flight: its `land` then includes the rest of the
    call ahead), `left_waiting` (riders still standing after this
    landing's boarding) and the phases of what that thread does, each
    an attribute and a profiler annotation of its own: `flight.land`
    (blocked for the device, then the fetch), `flight.board`,
    `flight.launch` (the launch of the call this landing boarded, if
    any), `flight.settle`. `flight.turnround` (`turnround_us`) is the
    host's share of the gap between this call and its successor: from
    `land`'s return to the next call's `launch` returning where this
    thread launched it onto the free chip (an upper bound: the device
    starts the call before `launch` returns), 0 where the successor
    was on the device's queue already; a call without a successor has
    none. The span is a child of the lander's own `device.call`; every
    rider's `device.call` names it (`flight`). After the boarded call
    is launched the phases go to `rendezvous_ns_total{family,phase}`,
    and `rendezvous_chained_total{family}` counts the calls that had a
    successor at their landing, launched already or by the landing
    thread: the ones that have a turn-round.
    `rendezvous_ahead_total{family}` counts the calls launched behind
    one still in flight, `rendezvous_calls_total{family}` every call
    launched and `rendezvous_riders_total{family}` the riders they
    carried."""

    _POLL_S = 0.05      # how often a waiter looks at its context
    _make = threading.Lock()

    def __init__(self, capacity: int, family: str = ""):
        self.capacity = capacity
        self.family = family
        self._cond = threading.Condition()
        # the device's queue of this tile's calls, in its order
        self._flight: _Flight | None = None     # the call on the chip
        self._behind: _Flight | None = None     # the one queued behind it
        self._waiting: list[Ride] = []

    @classmethod
    def at(cls, tile, capacity: int, family: str = "",
           key: str = "") -> "Rendezvous":
        """The tile's own rendezvous of `family`, made on first
        asking; `family` labels its flights' span and counters. `key`
        tells apart the calls of one family that cannot share a
        program (the vector scan's metric): one rendezvous each."""
        attr = f"_rendezvous_{family}_{key}" if key \
            else "_rendezvous_" + family
        meet = getattr(tile, attr, None)
        if meet is None:
            with cls._make:
                meet = getattr(tile, attr, None)
                if meet is None:
                    meet = cls(capacity, family)
                    setattr(tile, attr, meet)
        return meet

    def _board(self, first: Ride | None = None) -> _Flight | None:
        """(under the lock) The call the waiters make NOW, oldest
        first, `first` among them: whoever waits where the chip is
        free; a full call and no less where one call is on it,
        launched, and none stands behind. None otherwise."""
        ahead = self._flight
        if ahead is not None and (
                self._behind is not None or not ahead.launched
                or len(self._waiting) < self.capacity):
            return None
        riders = self._waiting[:self.capacity]
        if not riders:
            return None
        if first is not None and first not in riders:
            riders[-1] = first
        self._waiting = [r for r in self._waiting if r not in riders]
        flight = _Flight(riders, ahead is not None)
        if ahead is None:
            self._flight = flight
        else:
            self._behind = flight
        for lane, r in enumerate(riders):
            r.flight, r.lane = flight, lane
        return flight

    def _leave(self, flight: _Flight) -> _Flight | None:
        """(under the lock) `flight` is off the device's queue; -> the
        call that stood behind it and moves up, if any."""
        if self._behind is flight:
            self._behind = None
        elif self._flight is flight:
            self._flight, self._behind = self._behind, None
            return self._flight
        return None

    def _launch(self, flight: _Flight, launch):
        """(outside the lock) Put `flight` on the device. Where that
        raises, its riders fail with the error, the call leaves the
        queue, and the error is handed back."""
        flight.t_launch = time.perf_counter_ns()
        try:
            flight.handle = launch([r.item for r in flight.riders])
        except BaseException as e:  # noqa: BLE001 -- its riders' to
            # raise; an interrupt is raised again by ride()
            with self._cond:
                self._settle(flight, None, e)
                self._leave(flight)
                self._cond.notify_all()
            return e
        _dispatched(+1)
        labels = {"family": self.family}
        inc_counter("rendezvous_calls_total", labels=labels)
        inc_counter("rendezvous_riders_total", len(flight.riders),
                    labels=labels)
        # the series is there at 0: a reader tells "none went ahead"
        # from "not served"
        inc_counter("rendezvous_ahead_total", int(flight.ahead),
                    labels=labels)
        flight.launched = True
        return None

    def _settle(self, flight: _Flight, results, error) -> None:
        """(under the lock) Every rider of `flight` gets its own
        element of `results`, or `error`."""
        if error is None and len(results) != len(flight.riders):
            error = RuntimeError(
                f"{len(results)} results for {len(flight.riders)} riders")
        for i, r in enumerate(flight.riders):
            r.error = error
            r.result = None if error is not None else results[i]
            r.done = True

    def ride(self, item, launch, land, ctx=None) -> Ride:
        me = Ride(item)
        cond = self._cond
        mine = None             # the flight this thread lands
        with cond:
            self._waiting.append(me)
            while not me.done:
                f = me.flight
                if f is None:
                    # the chip is free, or my arrival filled the call
                    # that goes behind the one in flight
                    mine = self._board(me)
                elif f.launched and f.lander is None:
                    mine = f
                if mine is not None:
                    mine.lander = me
                    break
                me.stood = True
                left = None if ctx is None else ctx.remaining()
                cond.wait(None if ctx is None else self._POLL_S
                          if left is None else min(self._POLL_S, left))
                if ctx is not None and not me.done \
                        and (me.flight is None
                             or me.flight.lander is not None):
                    # still standing, or somebody else lands my call:
                    # free to go
                    try:
                        ctx.check("device rendezvous")
                    except BaseException:
                        if me.flight is None:
                            self._waiting.remove(me)
                        raise
        if mine is not None:
            if not mine.launched:
                self._launch(mine, launch)
            if mine.launched:
                self._fly(mine, launch, land)
        if me.error is not None:
            raise me.error
        return me

    def _fly(self, mine: _Flight, launch, land) -> None:
        """(the landing thread, outside the lock) Land `mine`; put the
        call the waiters make now, if any, on the device; then hand
        `mine`'s results out."""
        cond = self._cond
        family = self.family
        flight_span = span("device.flight", family=family,
                           lanes=len(mine.riders), ahead=mine.ahead)
        with flight_span as a:
            mine.span_id = flight_span.span_id
            t0 = time.perf_counter_ns()
            with _annotation("flight.land"):
                try:
                    results, error = land(mine.handle,
                                          len(mine.riders)), None
                except BaseException as e:
                    results, error = None, e
                finally:
                    _dispatched(-1)
            # the call behind, if any, has the chip already; the
            # waiters' call goes on it, or behind that one, before
            # anything is handed out
            t1 = time.perf_counter_ns()
            with _annotation("flight.turnround"):
                with _annotation("flight.board"), cond:
                    succ = self._leave(mine)
                    queued = succ is not None and succ.launched
                    nxt = self._board()
                    left = len(self._waiting)
                t2 = time.perf_counter_ns()
                failed = None
                if nxt is not None:
                    with _annotation("flight.launch"):
                        failed = self._launch(nxt, launch)
                t3 = time.perf_counter_ns()
            with _annotation("flight.settle"), cond:
                self._settle(mine, results, error)
                cond.notify_all()
            t4 = time.perf_counter_ns()
            phases = [("land", t1 - t0), ("board", t2 - t1),
                      ("settle", t4 - t3)]
            if nxt is not None:
                phases.append(("launch", t3 - t2))
            if queued or nxt is not None:
                # the host's share of the gap to the successor: none
                # where the device had it queued
                phases.append(("turnround", 0 if queued else t3 - t1))
                inc_counter("rendezvous_chained_total",
                            labels={"family": family})
            a["left_waiting"] = left
            for phase, ns in phases:
                a[phase + "_us"] = ns // 1000
                inc_counter("rendezvous_ns_total", ns,
                            labels={"family": family, "phase": phase})
            if failed is not None and not isinstance(failed, Exception):
                raise failed    # an interrupt is this thread's too
