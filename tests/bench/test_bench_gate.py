"""The harness measures on a TPU it knows the peaks of, or not at all."""

import types

import bench_testroot  # noqa: F401
import pytest

from bench import harness


def test_cpu_backend_is_refused():
    with pytest.raises(harness.NoChip, match="not a TPU"):
        harness.gate(1, backend="cpu")


def test_too_few_chips_are_refused():
    one = [types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")]
    with pytest.raises(harness.NoChip, match="asks for 4 chips"):
        harness.gate(4, backend="tpu", devices=one)
    assert harness.gate(1, backend="tpu", devices=one) == one


def test_unknown_device_kind_is_refused():
    bench = harness.Bench(bench_testroot.REPO)
    assert bench.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.NoChip, match="not in bench/peaks.json"):
        bench.peaks("TPU v9 imaginary")


def test_refusal_exits_nonzero_without_a_line(capsys):
    with pytest.raises(SystemExit) as e:
        raise harness.NoChip("no chip")
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
