"""trace_reduce on a trace recorded on a TPU v5e (three 4096x4096 bf16
matmuls, each inside a bench.step span of a bench.window) and on a
synthetic two-device trace."""
from pathlib import Path

import pytest

import trace_reduce

RECORDED = Path(__file__).with_name("v5e_matmul.xplane.pb")


def test_recorded_v5e_trace():
    ev = trace_reduce.load(str(RECORDED))
    assert list(ev["devices"]) == ["/device:TPU:0"]
    assert [n for n, _, _ in ev["devices"]["/device:TPU:0"]] == \
        ["%copy-start", "%copy-done", "%convolution_reduce_fusion"] * 3
    assert [n for n, _, _ in ev["host"]] == ["bench.window"] + ["bench.step"] * 3
    r = trace_reduce.reduce(ev)
    # window [44194320, 48634890] ns. The first matmul's events end before
    # it on the trace's clock; the other two clip to
    # [44731706, 44731719] + [44731721, 45436238] = 13 + 704517 ns and
    # [46077801, 46077814] + [46077816, 46782336] = 13 + 704520 ns
    assert r["window_s"] == pytest.approx(4440570e-9, abs=1e-12)
    assert r["busy_s"] == pytest.approx(1409063e-9, abs=1e-12)
    assert r["idle_share"] == pytest.approx(1 - 1409063 / 4440570)
    assert r["device_ops"][0] == ["%convolution_reduce_fusion",
                                  pytest.approx(1409029e-9, abs=1e-12)]
    assert r["idle_gaps"] == [["step", pytest.approx(
        (4440570 - 1409063) * 1e-9, abs=1e-12)]]


def test_two_devices_gaps_labelled_by_innermost_span():
    ev = {"devices": {"/device:TPU:0": [["%a", 0, 40], ["%b", 30, 60]],
                      "/device:TPU:1": [["%a", 10, 20]]},
          "host": [["bench.window", 0, 100], ["bench.cycle", 0, 100],
                   ["bench.migrate", 65, 90]]}
    r = trace_reduce.reduce(ev)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((60 + 10) / 2 * 1e-9)
    # device 0: gap 60-100 (mid 80, in migrate); device 1: 0-10 and
    # 20-100 (mid 50, in cycle only)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"migrate": 40 / 2 * 1e-9, "cycle": 90 / 2 * 1e-9})
    assert dict(r["device_ops"]) == pytest.approx(
        {"%a": 50 / 2 * 1e-9, "%b": 30 / 2 * 1e-9})


def test_no_device_operations_is_an_error():
    with pytest.raises(ValueError, match="no device operations"):
        trace_reduce.reduce({"devices": {}, "host": [["bench.window", 0, 1]]})
