"""The reduction from a device trace to idle share and breakdown: on
hand-made intervals, and on ``recorded_trace.json.gz``, the first ops of a
``/debug/tpu-trace`` capture taken on the v5e under ``mistral-7b.chat``."""

import os

import pytest

from bench_paths import SUITE
from benchmark.harness import cells, trace as tr
from benchmark.harness.rundata import RunData

RECORDED = os.path.join(SUITE, "recorded_trace.json.gz")


def run_with(trace):
    return RunData(seconds=1.0, records=[], prom_start={}, prom_end={},
                   prom_samples=[], endpoints={}, trace=trace)


def test_idle_share_and_self_times_on_hand_made_intervals():
    # One chip, 1 ms span: a 600 us while loop that encloses two fusions
    # (200 us + 100 us), a 50 us gap, a 150 us kernel, idle to the end.
    ops = [
        ("while.1", 0.0, 600e3), ("fusion.1", 100e3, 200e3),
        ("fusion.2", 400e3, 100e3), ("flash_kernel", 650e3, 150e3),
        ("fusion.1", 990e3, 10e3),
    ]
    trace = tr.DeviceTrace(
        devices={"/device:TPU:0": sorted(ops, key=lambda o: o[1])},
        modules={"/device:TPU:0": [("jit_decode_window(123)", 0.0, 800e3),
                                   ("jit_prefill(9)", 990e3, 10e3)]},
    )
    assert trace.window_s() == pytest.approx(1e-3)
    assert tr.busy_intervals(trace.devices["/device:TPU:0"]) == [
        (0.0, 600e3), (650e3, 800e3), (990e3, 1000e3),
    ]
    assert tr.busy_s(trace) == pytest.approx(760e-6)  # nested ops count once
    idle = cells.load_module("readers", "trace_idle")
    assert idle.read(run_with(trace)) == pytest.approx(0.24)
    top = cells.load_module("readers", "trace_top_ops")
    breakdown = top.read(run_with(trace))
    listed = dict(map(tuple, breakdown["device_ops"]))
    assert listed == pytest.approx({
        "module jit_decode_window": 800e-6, "module jit_prefill": 10e-6,
        "op while.1": 300e-6, "op fusion.1": 210e-6, "op flash_kernel": 150e-6,
        "op fusion.2": 100e-6,
    })
    ops_s = [s for name, s in listed.items() if name.startswith("op ")]
    assert sum(ops_s) == pytest.approx(tr.busy_s(trace))  # self times add up
    assert [n for n, _ in breakdown["device_ops"]][:3] == [
        "module jit_decode_window", "module jit_prefill", "op while.1",
    ]  # programs first, then operations, most time first
    gaps = dict(map(tuple, breakdown["idle_gaps"]))
    assert gaps == pytest.approx({
        "unattributed_under_100us": 50e-6, "unattributed_100us_to_1ms": 190e-6,
    })
    # Two chips: busy time is the mean over chips, on one common span.
    both = tr.DeviceTrace(devices={
        "/device:TPU:0": [("a", 0.0, 1000.0)], "/device:TPU:1": [("a", 0.0, 500.0)],
    })
    assert tr.busy_s(both) == pytest.approx(750e-9)
    assert idle.read(run_with(both)) == pytest.approx(0.25)


def test_no_capture_or_no_device_plane_reads_nothing():
    idle = cells.load_module("readers", "trace_idle")
    top = cells.load_module("readers", "trace_top_ops")
    for trace in (None, tr.DeviceTrace(devices={})):
        assert idle.read(run_with(trace)) is None
        assert top.read(run_with(trace)) is None


def test_round_trip_through_the_recorded_form_and_short_names():
    trace = tr.DeviceTrace(
        devices={"/device:TPU:0": [("x", 5.0, 2.0), ("y", 9.0, 1.0)]},
        modules={"/device:TPU:0": [("jit_f(1)", 5.0, 5.0)]},
    )
    again = tr.DeviceTrace.from_json(trace.to_json())
    assert again == trace and again.span_ns == (5.0, 10.0)
    hlo = ("%fusion.322 = bf16[14,4096]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[14,4096]"
           "{1,0} %get-tuple-element.3137), kind=kOutput, calls=%fused_computation.74")
    assert tr.short_name(hlo) == "%fusion.322 bf16[14,4096]"
    assert tr.short_name("jit_decode_window(7887516207321774033)") == "jit_decode_window"
    assert tr.short_name("flash_kernel") == "flash_kernel"


def test_reduction_on_the_trace_recorded_on_the_v5e():
    """One prefill chunk step and the speculative window after it, whole
    (33,883 op events with their nesting), cut from a 2 s capture under
    ``mistral-7b.chat`` on the TPU v5e (my chip run, PR 24)."""
    trace = tr.read_recorded(RECORDED)
    (ops,) = trace.devices.values()
    assert len(ops) == 33883 and trace.window_s() == pytest.approx(0.638596, rel=1e-5)
    run = run_with(trace)
    idle = cells.load_module("readers", "trace_idle").read(run)
    assert 0 <= idle < 1e-4  # back to back: the device never waited here
    breakdown = cells.load_module("readers", "trace_top_ops").read(run)
    listed = dict(map(tuple, breakdown["device_ops"]))
    assert len(breakdown["device_ops"]) == 10
    assert listed["module jit_spec_window"] == pytest.approx(0.375126, rel=1e-5)
    assert listed["module jit_prefill_chunk_step_hist"] == pytest.approx(0.263467, rel=1e-5)
    # the decode window's down projection over all 32 layers, 8 steps x 3 forwards
    assert listed["op %fusion.322 bf16[14,4096]"] == pytest.approx(0.061336, rel=1e-4)
    # self times: the enclosing whiles are charged only what they do not cover
    per_op = top_self_times(ops)
    assert sum(per_op.values()) == pytest.approx(tr.busy_s(trace), rel=1e-9)
    assert max(s for n, s in per_op.items() if n.startswith("%while")) < 0.002
    kernel = [n for n in per_op if n.startswith("%flash_cache_attention")]
    assert kernel and tr.short_name(kernel[0]).startswith(
        "%flash_cache_attention.7 bf16[8,8,1024,128]"
    )  # a Pallas kernel keeps its function's name
    assert breakdown["idle_gaps"] == [
        ["unattributed_under_100us", pytest.approx(4.153e-06, rel=1e-3)]
    ]


def top_self_times(ops):
    return cells.load_module("readers", "trace_top_ops").self_times(ops)
