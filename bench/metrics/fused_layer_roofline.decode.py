"""Least time of the paged decode megakernel (from counts of its work in
the traced window) over its summed device time (from the trace)."""

import counts
import trace_reduce

KERNEL = "fused_decode_layer_paged"


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    kernel_s = trace_reduce.kernel_seconds(red, KERNEL)
    if kernel_s <= 0:
        return None
    c, peaks = ctx["config"], ctx["peaks"]
    t0, t1 = ctx["trace_window"]
    least = 0.0
    bounds = {"compute": 0, "memory": 0}
    for t_done, lives in ctx["steps"]:
        if t0 <= t_done <= t1 and lives:
            ops = counts.lm_step_layer_ops(c, lives)
            nbytes = counts.lm_step_layer_bytes(c, lives)
            t, bound = counts.least_time_s(ops, nbytes, peaks)
            least += c["num_layers"] * t
            bounds[bound] += 1
    ctx.setdefault("notes", {})["fused_layer_roofline.decode"] = bounds
    return 100.0 * least / kernel_s
