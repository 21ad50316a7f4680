"""The trace reduction and each metric reader, on a trace recorded on a
TPU v5e (a 0.32 s slice of a paper-bmode.backlog window) and on small
hand-made traces."""

import json
import os
import types

import pytest

from bench import spec, trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "v5e-bmode-backlog-events.json")


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def hand_made():
    # window 0..1000 ns, trimmed 10% -> slice 100..900 (800 ns)
    return {"device": {"/device:TPU:0": [
                ["%a = f32[4]{0} fusion(f32[4] %x)", 0, 300],
                ["%b = f32[4]{0} add(f32[4] %x)", 200, 400],  # overlaps a
                ["%a = f32[4]{0} fusion(f32[4] %x)", 600, 700]]},
            "host": [["main", "TransferToDevice", 380, 620],
                     ["main", "Execute", 0, 1000]],
            "markers": {trace.OPEN: [0], trace.LANDED: [500, 1000]}}


def test_union_clip_and_gaps_on_a_hand_made_trace():
    s = trace.summarize(hand_made())
    assert s["window_s"] == pytest.approx(800e-9)
    assert s["busy_s"] == pytest.approx(400e-9)   # 100..400 and 600..700
    assert s["device_ops"][0] == ["%a f32[4] fusion", pytest.approx(300e-9)]
    # gaps 400..600 (the transfer overlaps it as much as the longer
    # Execute does, and is the more specific) and 700..900
    assert [[n, round(g * 1e9)] for n, g in s["idle_gaps"]] == [
        ["main: TransferToDevice", 200], ["main: Execute", 200]]


def test_recorded_trace_reduces_to_a_busy_device(recorded):
    s = trace.summarize(recorded)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["busy_s"] / s["window_s"] > 0.99
    assert len(s["device_ops"]) == 10 and len(s["idle_gaps"]) <= 10
    names = [n for n, _ in s["device_ops"]]
    assert any(n.endswith(" slice") for n in names)
    assert sum(t for _, t in s["device_ops"]) <= s["busy_s"] * 1.0001


def test_trace_without_markers_or_device_ops_is_refused(recorded):
    with pytest.raises(ValueError):
        trace.summarize(dict(recorded, markers={}))
    with pytest.raises(ValueError):
        trace.summarize(dict(recorded, device={}))


def ctx(summary):
    stats = {"queue_delay": {"p95_s": 0.012},
             "occupancy": {"mean_occupancy": 2.5, "batches": 40},
             "h2d_s": 0.08}
    return types.SimpleNamespace(
        stats=stats, window_s=10.0, latencies_s=[0.01] * 19 + [0.05],
        served=100, served_bytes=100 * 5_472_256, setup_s=12.5,
        flops=100 * 640_000_000, trace=summary, device_kind="TPU v5 lite",
        chips=1)


def test_each_reader(recorded):
    s = trace.summarize(recorded)
    c = ctx(s)
    want = {
        "throughput_MBps": 54.72256,
        "latency_p50_ms": 10.0,
        "latency_p95_ms": 12.0,
        "setup_s": 12.5,
        "queue_delay_p95_ms": 12.0,
        "batch_occupancy_mean": 2.5,
        "place_host_ms_per_batch": 2.0,
        "device_idle_frac": 1 - s["busy_s"] / s["window_s"],
        "device_ms_per_acq": s["busy_s"] / s["window_s"] * 100.0,
        "pipeline_mfu_pct": 100 * 640e6 / 10.0 / 197e12 * 100,
    }
    for name, value in want.items():
        assert spec.reader(name)(c) == pytest.approx(value), name


def test_trace_readers_stay_silent_without_a_trace():
    c = ctx(None)
    for name in ("device_idle_frac", "device_ms_per_acq"):
        assert spec.reader(name)(c) is None


def test_peak_share_of_an_unknown_device_is_an_error(recorded):
    c = ctx(trace.summarize(recorded))
    c.device_kind = "TPU v99"
    with pytest.raises(KeyError):
        spec.reader("pipeline_mfu_pct")(c)
