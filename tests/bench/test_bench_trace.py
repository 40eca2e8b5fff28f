"""The reduction from a trace to per-layer numbers: busy union, idle share,
kernel time found by name, the program that holds a kernel, and idle gaps
named by the host span around them."""

import bench_testroot  # noqa: F401
import pytest

from bench import trace


def _rec():
    # window 0..100; two fusions back to back, a kernel call, a gap 40-60
    # under the host span "bench.engine_step", an operation past the window
    return {
        "window": [0, 100],
        "devices": [{
            "name": "/device:TPU:0",
            "ops": [["%fusion.1 = f32[8]", 10, 20, None],
                    ["%fusion.2 = f32[8]", 30, 10, None],
                    ["%flash_decode_paged.3 = bf16[8] custom-call()", 60, 30,
                     "flash_decode_paged"],
                    ["%copy.4 = f32[8]", 95, 10, None]],
            "modules": [["jit_step(1)", 55, 40], ["jit_other(2)", 8, 34]]}],
        "host": [["bench.window", 0, 100], ["bench.engine_step", 35, 30]],
    }


def test_busy_union_and_idle_share():
    rec = _rec()
    assert trace.busy_intervals(rec["devices"][0], rec["window"]) == \
        [(10, 40), (60, 90), (95, 100)]
    assert trace.busy_s(rec) == pytest.approx(65e-9)
    assert trace.idle_share(rec) == pytest.approx(35.0)
    assert trace.window_s(rec) == pytest.approx(100e-9)


def test_kernel_found_by_name_and_its_program():
    rec = _rec()
    assert trace.kernel_calls(rec, "flash_decode_paged") == [30]
    assert trace.programs_with(rec, "flash_decode_paged") == [40]
    assert trace.programs_with(rec, "fd2d") == []


def test_breakdown_names_gaps_by_host_span():
    b = trace.breakdown(_rec())
    assert b["device_ops"] == [["fusion", pytest.approx(30e-9)],
                               ["flash_decode_paged", pytest.approx(30e-9)],
                               ["copy", pytest.approx(5e-9)]]
    assert b["idle_gaps"][0] == ["bench.engine_step", pytest.approx(20e-9)]
    assert ["untraced", pytest.approx(10e-9)] in b["idle_gaps"]


def test_kernel_name_from_the_operation_text():
    text = ('%fd2d.1 = f32[8192,8192]{1,0:T(8,128)} custom-call(f32[10240,'
            '12288]{1,0:T(8,128)} %bitcast), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={}')
    assert trace.kernel_of(text) == "fd2d"
    assert trace.kernel_of('%flash_decode_paged.5 = bf16[24,16,1,128] '
                           'custom-call(s32[24,4] %c), custom_call_target='
                           '"tpu_custom_call"') == "flash_decode_paged"
    assert trace.kernel_of("%fusion.3 = f32[8] fusion(f32[8] %p)") is None
    assert trace.op_name("%copy_dynamic-update-slice_fusion.7 = bf16[2]") \
        == "copy_dynamic-update-slice_fusion"
    assert trace.op_name("%and_bitcast_fusion = pred[24]") == \
        "and_bitcast_fusion"


def test_self_time_leaves_out_nested_operations():
    dev = {"ops": [["%while.3 = (s32[])", 0, 100, None],
                   ["%fusion.1 = f32[2]", 10, 20, None],
                   ["%k.2 = f32[2] custom-call()", 40, 30, "k"],
                   ["%copy.9 = f32[2]", 120, 10, None]]}
    assert trace.self_times(dev, [0, 200]) == [["while", 50], ["fusion", 20],
                                               ["k", 30], ["copy", 10]]


# Recorded on a TPU v5e (the benchmark's own traced runs, trimmed): two
# engine decode steps of internlm2-1.8b at 24 slots, and three fd2d steps at
# 8192^2. Operation texts are cut to 64 characters; the kernel column is
# what ``trace.load`` found in the full text.
FIXTURES = bench_testroot.REPO / "tests/bench/fixtures"


def _recorded(name):
    import json

    return json.loads((FIXTURES / name).read_text())


def _union_by_microsecond(rec):
    import numpy as np

    lo, hi = rec["window"]
    busy = np.zeros((hi - lo) // 1000 + 1, bool)
    for _, s, d, _ in rec["devices"][0]["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            busy[(a - lo) // 1000:(b - lo) // 1000] = True
    return busy.sum() * 1e-6


@pytest.mark.parametrize("name,kernel,calls,programs", [
    ("trace_decode_step.json", "flash_decode_paged", 48, 2),
    ("trace_fd2d_step.json", "fd2d", 3, 2)])   # one program began earlier
def test_recorded_trace(name, kernel, calls, programs):
    rec = _recorded(name)
    assert trace.busy_s(rec) == pytest.approx(_union_by_microsecond(rec),
                                              rel=2e-3)
    idle = 100 * (1 - trace.busy_s(rec) / trace.window_s(rec))
    assert trace.idle_share(rec) == pytest.approx(idle)
    got = trace.kernel_calls(rec, kernel)
    want = [d for _, s, d, k in rec["devices"][0]["ops"]
            if k == kernel and rec["window"][0] <= s < rec["window"][1]]
    assert len(got) == calls and sum(got) == sum(want) > 0
    runs = trace.programs_with(rec, kernel)
    assert len(runs) == programs
    # a program that holds the kernel lasts at least its kernel calls
    assert sum(runs) >= sum(got)
    ops = trace.top_ops(rec)
    assert kernel in [n for n, _ in ops]
    assert sum(t for _, t in ops) <= trace.busy_s(rec) * 1.0001


def test_recorded_decode_step_holds_its_kernels():
    rec = _recorded("trace_decode_step.json")
    kinds = {k for _, _, _, k in rec["devices"][0]["ops"] if k}
    assert kinds == {"flash_decode_paged", "rmsnorm", "lm_head_logits"}
    # 24 layers, 2 steps: one paged decode call per layer and step
    assert len(trace.kernel_calls(rec, "flash_decode_paged")) == 2 * 24
