"""Mean host time from one decode step's token read-back to the next
step's dispatch, over the turnarounds of the window (the scheduler's
``ServeStats``; none without that counter)."""


def read(ctx):
    st, st0 = ctx["stats"], ctx["stats0"]
    if not hasattr(st, "turnarounds"):
        return None
    n = st.turnarounds - st0.turnarounds
    if n <= 0:
        return None
    return 1e3 * (st.host_turnaround_s - st0.host_turnaround_s) / n
