"""Kernels: what the chips spend handing each other the frontier, as
a share of the sharded k-hop traversal's device time: the device time
of the collective ops inside `jit_bfs_traverse_sharded`
(ops/bitgraph.py's bfs_traverse_sharded: ONE collective a level, an
all-gather of the chips' shares of the level's reach, a lane word a
covered vertex) over the program's, both averaged over the chips as
trace_reduce.py gives them. An op is a collective by the name XLA
prints it under (all-gather, all-reduce, all-to-all,
collective-permute, reduce-scatter, with their -start and -done
halves). The reduction keeps a trace's thirty longest ops: a
collective shorter than all of those is not among them, and the
reader then says nothing rather than 0. None too where no such program
ran (a one-chip program, an older one)."""

PROGRAM = "jit_bfs_traverse_sharded"
COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter",
               "collective-broadcast")


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr.get("programs") or not tr.get("device_ops"):
        return None
    seconds = sum(s for name, s, _ in tr["programs"]
                  if name.startswith(PROGRAM))
    if seconds <= 0:
        return None
    ops = [(name.split("/", 1)[1], s) for name, s in tr["device_ops"]
           if name.startswith(PROGRAM) and "/" in name]
    between = [(op, s) for op, s in ops if op.startswith(COLLECTIVES)]
    if not between:
        return None
    spent = sum(s for _, s in between)
    ctx["notes"].append(
        f"collective: {PROGRAM}: "
        + ", ".join(f"{op} {s:.6f} s" for op, s in between)
        + f" of {seconds:.6f} s a chip")
    return 100.0 * spent / seconds
