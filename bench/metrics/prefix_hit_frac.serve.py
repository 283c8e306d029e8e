"""Prompt positions served from shared pages over prompt positions
admitted, counted by the scheduler over the window."""


def read(ctx):
    st, st0 = ctx["stats"], ctx["stats0"]
    hit = st.prefix_hit_tokens - st0.prefix_hit_tokens
    fed = st.prefill_tokens - st0.prefill_tokens
    if hit + fed <= 0:
        return None
    return hit / (hit + fed)
