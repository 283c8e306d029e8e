"""Share of the device's busy time spent in Mosaic (Pallas) kernels, the
rest being XLA around them (packing, comparator draws, layout)."""


def read(ctx):
    red = ctx["trace"]
    if red is None or red["busy_s"] <= 0:
        return None
    return 100.0 * sum(red["kernel_s"].values()) / sum(red["op_s"].values())
