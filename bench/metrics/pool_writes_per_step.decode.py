"""Executions of the helpers that rewrite the whole paged state (page-table
writes, page copies and zeroing, slot admission and release) per decode
step in the window (the scheduler's ``ServeStats``; none without that
counter)."""


def read(ctx):
    st, st0 = ctx["stats"], ctx["stats0"]
    if not hasattr(st, "pool_writes"):
        return None
    steps = st.decode_steps - st0.decode_steps
    if steps <= 0:
        return None
    return (st.pool_writes - st0.pool_writes) / steps
