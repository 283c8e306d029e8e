"""Model operations of every position the decode steps computed in the
traced window, over the window and the chip's int8 peak."""

import counts


def read(ctx):
    if ctx["trace"] is None:
        return None
    c, peaks = ctx["config"], ctx["peaks"]
    t0, t1 = ctx["trace_window"]
    ops = sum(counts.lm_position_ops(c, live)
              for t_done, lives in ctx["steps"] if t0 <= t_done <= t1
              for live in lives)
    if not ops:
        return None
    return 100.0 * ops / ((t1 - t0) * peaks["int8_ops"])
