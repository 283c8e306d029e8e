"""The trace reducer on a small recorded trace: 20 steps of the paged
gpt-8-512 server (64 slots) profiled on a TPU v5 lite, and on made-up
events whose answers are worked by hand."""

import gzip
from pathlib import Path

import pytest

import trace_reduce as TR

DATA = Path(__file__).resolve().parent / "data" / "decode_20_steps.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(DATA.read_bytes()))
    return TR.reduce(path, window_s=2.5)


def test_recorded_trace_programs_and_kernels(recorded):
    red = recorded
    assert red["devices"] == 1
    secs, runs = TR.module_seconds(red, "jit_step")
    assert runs == 20 and secs == pytest.approx(1.3239, rel=1e-3)
    assert TR.module_seconds(red, "jit_paged_set_table_entry")[1] == 197
    # the paged megakernel, once per layer per step
    assert TR.kernel_seconds(red, "fused_decode_layer_paged") == pytest.approx(
        0.6101, rel=1e-3)
    # busy is the union of device operations, never above the programs' time
    assert red["busy_s"] == pytest.approx(2.0325, rel=1e-3)
    assert red["busy_s"] <= sum(red["module_s"].values()) + 1e-9
    names = [k for k, _ in red["breakdown"]["device_ops"]]
    assert names[:2] == ["jit_paged_set_table_entry/copy",
                         "jit_step/fused_decode_layer_paged"]
    assert "jit_step/while" not in names  # loop bodies count as self time
    assert len(red["breakdown"]["idle_gaps"]) == 10


def test_made_up_events():
    dev = [("/device:TPU:0", {
        TR.MODULES_LINE: [("jit_step(1)", 0.0, 100.0)],
        TR.OPS_LINE: [
            ("%while.1 = (...) while(...)", 0.0, 60.0),
            ("%fusion.2 = f32[4] fusion(...)", 10.0, 20.0),
            ("%my_kernel.3 = u8[4] custom-call(...)", 35.0, 20.0),
            ("%copy.4 = u8[4] copy(...)", 80.0, 20.0),
        ]})]
    host = [("bench.window_open", 0.0, 0.0), ("bench.window_close", 120.0, 0.0),
            ("bench.sched_step", 55.0, 30.0)]
    red = TR.reduce_events(dev, host, window_s=1.0)
    assert red["window_s"] == pytest.approx(120e-9)
    assert red["busy_s"] == pytest.approx(80e-9)  # [0, 60) and [80, 100)
    assert red["op_s"]["jit_step/while"] == pytest.approx(20e-9)  # 60 - 20 - 20
    assert red["kernel_s"] == {"jit_step/my_kernel": pytest.approx(20e-9)}
    gaps = red["breakdown"]["idle_gaps"]
    assert len(gaps) == 2
    assert ["none", pytest.approx(20e-9)] in gaps  # [100, 120): no span
    assert ["bench.sched_step", pytest.approx(20e-9)] in gaps  # [60, 80)
