"""Kernels: roofline share of the vector scan, the device program
ops/knn.py's topk_device dispatches (`jit__topk_device_jit`, every
shape of it): calls x the resident block's bytes (the gauge
`device_vector_block_bytes`), over the chip's HBM bandwidth, over the
program's device time in the trace. Memory-bound by statement at
these batch sizes (one query: 1.5 GFLOP in six bf16 passes is 8 us of
matrix unit, 512 MB is 0.63 ms of HBM). The bytes are a lower bound,
the block once a call and nothing else, so the share cannot pass 100%.
None where the program serves no such gauge or ran no such program."""

PROGRAM = "jit__topk_device_jit"
GAUGE = "device_vector_block_bytes"


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr.get("programs") or not ctx["peaks"]:
        return None
    block = sum(v for k, v in ctx["counters_after"].items()
                if k.startswith(GAUGE))
    seconds = calls = 0
    for name, s, n in tr["programs"]:
        if name.startswith(PROGRAM):
            seconds, calls = seconds + s, calls + n
    if block <= 0 or seconds <= 0:
        return None
    least_s = calls * block / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["notes"].append(
        f"roofline: {PROGRAM}: {calls} calls, {block:.0f} B each at "
        f"least, {seconds:.6f} s on the device, {least_s:.6f} s at "
        f"{ctx['peaks']['hbm_bytes_per_s']:.3g} B/s")
    return 100.0 * least_s / seconds
