"""Page columns holding live positions over the page columns the paged
attention kernel's grid visits, per layer, summed over the window's steps
(the scheduler's ``ServeStats``; none without those counters)."""


def read(ctx):
    st, st0 = ctx["stats"], ctx["stats0"]
    if not hasattr(st, "page_cols_visited"):
        return None
    visited = st.page_cols_visited - st0.page_cols_visited
    if visited <= 0:
        return None
    return (st.page_cols_live - st0.page_cols_live) / visited
