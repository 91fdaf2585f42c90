"""The trace reduction: busy and idle time, time per device operation and
program, and idle gaps named by the host's innermost span; on a small
synthetic trace, and on a trace recorded on a TPU v5e."""
import gzip
import json

import pytest

import benchtools
from bench import trace

MS = 1_000_000   # ns
F1, F2 = "%fusion.1 fusion f32[8]", "%fusion.2 fusion f32[8]"
CC = "%closed_call.3 custom-call tuple tpu_custom_call"


def _synthetic():
    # window 0..100 ms; two devices; ops overlap on device 0
    dev0 = {"name": "/device:TPU:0",
            "ops": [[F1, 10 * MS, 20 * MS],
                    [F2, 20 * MS, 20 * MS],             # overlaps F1
                    ["%while.4 while tuple", 10 * MS, 30 * MS],
                    [CC, 70 * MS, 10 * MS],
                    [F1, 95 * MS, 10 * MS]],            # cut by the window
            "modules": [["jit__decode(7)", 10 * MS, 30 * MS],
                        ["jit__decode(7)", 70 * MS, 10 * MS]]}
    dev1 = {"name": "/device:TPU:1", "ops": [[F1, 0, 50 * MS]],
            "modules": []}
    return {"window_ns": [0, 100 * MS], "host_window": [5.0, 5.1],
            "devices": [dev0, dev1]}


def test_busy_is_the_union_of_operations_in_the_window():
    red = trace.reduce(_synthetic())
    # device 0: 10..40 and 70..80 and 95..100 -> 45 ms; device 1: 50 ms
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx((0.045 + 0.050) / 2)


def test_time_per_operation_and_program():
    red = trace.reduce(_synthetic())
    assert red["ops"][F1] == pytest.approx((0.02 + 0.01 + 0.05) / 2)
    assert red["ops"][CC] == pytest.approx(0.005)
    assert "%while.4 while tuple" not in red["ops"]   # runs others only
    assert red["programs"] == {"jit__decode": [0.03, 0.01]}
    top = red["breakdown"]["device_ops"]
    assert top[0][0] == F1 and len(top) <= 10


def test_op_labels():
    text = ('%copy.67 = f32[32,64,40,64,64]{4,3,2,1,0:T(8,128)} copy('
            'f32[32,64,40,64,64]{4,3,2,1,0:T(8,128)} %get-tuple-element.6)')
    assert trace.op_label(text) == "%copy.67 copy f32[32,64,40,64,64]"
    text = ('%closed_call.27 = (f32[256,17]{1,0}, f32[256,1]{1,0}) '
            'custom-call(f32[256,1]{1,0} %copy.129), '
            'custom_call_target="tpu_custom_call"')
    assert trace.op_label(text) == \
        "%closed_call.27 custom-call tuple tpu_custom_call"
    assert trace.op_label("jit__decode(42)") == "jit__decode(42)"


def test_idle_gaps_are_named_by_the_innermost_host_span():
    tr = _synthetic()
    host = [["serve.call", 0, 100 * MS],
            ["serve.decode", 38 * MS, 41 * MS],
            ["serve.token_fetch", 41 * MS, 69 * MS],
            ["serve.decode", 69 * MS, 71 * MS]]
    red = trace.reduce(tr, host)
    idle = red["idle_by_host"]
    # device 0 gaps: 0..10 (call), 40..70 (mid 55: token_fetch),
    # 80..95 (call); device 1: 50..100 (mid 75: call)
    assert idle["serve.token_fetch"] == pytest.approx(0.030 / 2)
    assert idle["serve.call"] == pytest.approx((0.010 + 0.015 + 0.050) / 2)
    assert sum(idle.values()) == pytest.approx(0.1 - red["busy_s"])
    assert trace.reduce(tr)["idle_by_host"] == {
        "host idle": pytest.approx(0.1 - red["busy_s"])}


def test_host_spans_map_onto_the_trace_clock():
    tr = _synthetic()                      # host 5.0 .. 5.1 s <-> 0 .. 100 ms
    spans = trace.host_spans(tr, [("a", 5.01, 5.02), ("b", 4.0, 4.5)])
    assert spans == [["a", 10 * MS, 20 * MS]]


def test_recorded_chip_trace():
    """A trace of `rwkv6.decode` recorded on one TPU v5e chip (a few decode
    steps, cut to 2 ms around a step boundary): the reduction's numbers
    agree with sums taken by hand from the same events."""
    path = benchtools.ROOT / "bench" / "testdata" / "trace_v5e_decode.json.gz"
    with gzip.open(path, "rt") as f:
        tr = json.load(f)
    red = trace.reduce(tr, tr["host"])
    (dev,) = tr["devices"]
    lo, hi = tr["window_ns"]
    ops = sorted((s, s + d) for _, s, d in dev["ops"])
    busy, end = 0, lo
    for s, e in ops:
        s, e = max(s, end, lo), min(e, hi)
        if e > s:
            busy += e - s
            end = e
    assert red["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert sum(red["idle_by_host"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert any("decode" in name for name in red["programs"])
    assert set(red["idle_by_host"]) <= {n for n, _, _ in tr["host"]} | {
        "host idle"}
