"""Device time inside executions of the batched decode step, per step
(from the trace's program executions)."""

import trace_reduce

PROGRAM = "jit_step"


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    secs, runs = trace_reduce.module_seconds(red, PROGRAM)
    return 1e3 * secs / runs if runs else None
