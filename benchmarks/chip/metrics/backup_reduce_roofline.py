"""backup_reduce_roofline: the in-shard masked reduce kernel's share of
its HBM roofline (%).

The kernel (``kernels/backup_reduce.py``) is a Pallas call: on a TPU
trace a ``tpu_custom_call`` named after the ``shard_map`` that calls it,
with empty kernel metadata, so its name cannot tell it from another
Pallas kernel. It is told by its HLO signature instead: the
[W_local, n] f32 gradient stack and the [W_local, 1] f32 mask in, the
[n] f32 mean out (one call per bucket). Any other custom call is not
counted. Each call reads its stack once and writes its mean once
(``flops.backup_reduce_bytes``, from the call's own shapes); the least
time per step is those bytes over the chip's HBM bandwidth, and the
share is that over the kernel's device time per step, from the trace,
averaged over the chips. Nothing to read where the kernel does not run.
"""
import re

SIGNATURE = re.compile(
    r"= f32\[(\d+)\]\S* custom-call\(f32\[(\d+),(\d+)\]\S* %\S+, "
    r"f32\[(\d+),1\]\S* %\S+\), custom_call_target=\"tpu_custom_call\"")


def read(ctx):
    w_local = ctx.traffic.total_workers // ctx.mesh_data
    found = {}
    for name, text in ctx.trace["custom_calls"].items():
        m = SIGNATURE.search(text)
        if m and int(m[2]) == int(m[4]) == w_local and m[1] == m[3]:
            found[name] = int(m[1])
    seconds = ctx.trace_lib.mean(ctx.trace_lib.op_s(ctx.trace,
                                                    found.__contains__))
    if not found or not seconds or ctx.steps <= 0:
        return None
    least = (sum(ctx.flops.backup_reduce_bytes(w_local, n)
                 for n in found.values()) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / ctx.steps)
