"""Each kind of cell run end to end on the CPU at a tiny size, with the
chip check skipped: correct when the program is sound, not correct when
the timed path is broken underneath, and the control (the reference in
bfloat16 in the program's place) reading above the cell's limit."""

import numpy as np


def test_decode_cell_is_correct(bench_at):
    res = bench_at("tiny.decode")
    assert res["correct"], res["checks"]
    assert res["metrics"]["tok_s"]["value"] > 0
    assert res["checks"]["max_logit_gap"]["value"] == 0.0


def test_serve_cell_is_correct_through_shared_prefixes(bench_at):
    seen = {}

    def server(sch):
        seen["sch"] = sch

    res = bench_at("tiny.serve", seconds=6.0, hooks={"server": server})
    assert res["correct"], res["checks"]
    assert seen["sch"].stats.prefix_hit_tokens > 0
    assert {"ttft_p95_ms", "itl_p95_ms", "setup_s"} <= set(res["metrics"])


def test_classify_cell_is_correct(bench_at):
    res = bench_at("tiny.classify")
    assert res["correct"], res["checks"]
    assert res["metrics"]["images_s"]["value"] > 0


def test_altered_token_is_not_correct(bench_at):
    """A token altered where the scheduler produces it."""
    def server(sch):
        step = sch.step

        def altered():
            n = step()
            for out in sch.outputs.values():
                if len(out) == 2:
                    out[1] = (out[1] + 1) % 64
            return n

        sch.step = altered

    res = bench_at("tiny.decode", hooks={"server": server})
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > res["checks"]["max_logit_gap"]["limit"]


def test_altered_label_is_not_correct(bench_at):
    """One image's logits reversed where the forward produces them."""
    def forward(fwd):
        def altered(params, x, rng):
            lg = fwd(params, x, rng)
            return lg.at[0].set(lg[0][::-1])
        return altered

    res = bench_at("tiny.classify", hooks={"forward": forward})
    assert not res["correct"]


def test_control_reads_above_the_limit(bench_at):
    """The reference in bfloat16 in the program's place fails the number
    each cell compares (on the chip at the cells' own sizes the readings
    are in PERF.md; this keeps the control runnable)."""
    for cell, key in (("tiny.decode", "max_logit_gap"),
                      ("tiny.classify", "max_logit_err")):
        got = {}

        def control(ref, params, c, served, cmp):
            got["limit"] = c["check"][key]
            got["sound"] = cmp[key]
            got["control"] = ref.control(params, c, served)[key]

        bench_at(cell, seed=11, hooks={"control": control})
        assert got["sound"] <= got["limit"] < got["control"], (cell, got)
        assert np.isfinite(got["control"])
