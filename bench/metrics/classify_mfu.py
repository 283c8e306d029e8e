"""Model operations per image times the images of every batch sent and
labelled inside the traced window, over those batches' time (each batch
runs from the previous label's arrival to its own) and the chip's int8
peak."""

import counts


def read(ctx):
    if ctx["trace"] is None:
        return None
    t0, t1 = ctx["trace_window"]
    n, span, prev = 0, 0.0, ctx["t0"]
    for _, t in ctx["done"]:
        if t0 <= prev and t <= t1:
            n += 1
            span += t - prev
        prev = t
    if not n:
        return None
    ops = n * ctx["batch"] * counts.vit_image_ops(ctx["config"])
    return 100.0 * ops / (span * ctx["peaks"]["int8_ops"])
