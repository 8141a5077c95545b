"""Executor: `similar_to` queries a device call of the vector scan's
rendezvous carried. `rendezvous_riders_total{family="similar"}`
(queries the calls carried) over
`rendezvous_calls_total{family="similar"}` (calls
`query/devicecall.Rendezvous` launched for
`executor._eval_similar_to_inner`, counted in `Rendezvous._launch`),
as deltas over the window: a mean a call, between 1 (every request
rode alone) and the program's `ops/knn.LANES` = 8. A closed loop of 8
clients cannot fill a call (one of them is on the chip), so the
reading says how many of the others were inside their `device_call`
block when a call landed. None where the program serves one of the
two series not at all (a program older than PR 46) or made no call in
the window."""

CALLS = 'rendezvous_calls_total{family="similar"}'
RIDERS = 'rendezvous_riders_total{family="similar"}'


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if CALLS not in a or RIDERS not in a:
        return None
    calls = a[CALLS] - b.get(CALLS, 0)
    if calls <= 0:
        return None
    return (a[RIDERS] - b.get(RIDERS, 0)) / calls
