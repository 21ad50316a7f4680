"""Stage scopes, executions and serving-loop spans (bench/stage_trace.py),
on small hand-made traces and on a whole traced window recorded on a TPU
v5e (paper-bmode.backlog, 0.5 s)."""

import json
import os

import pytest

from bench import stage_trace, trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "v5e-bmode-backlog-stages.json")


@pytest.mark.parametrize("op_name,scope", [
    ("jit(run)/vmap(demod)/slice", "demod"),
    ("jit(run)/vmap(beamform)/cps,scfr->pfr/dot_general", "beamform"),
    ("jit(run)/bmode/sqrt", "bmode"),
    ("jit(run)/shard_map/vmap(power_doppler)/add", "power_doppler"),
    ("jit(run)/doppler/broadcast_in_dim;jit(run)/doppler/reshape",
     "doppler"),
    ("jit(run)/demod+beamform+bmode/pallas_call", "demod+beamform+bmode"),
    ("jit(run)/vmap(demod)/jit(_pad)/pad", "demod"),
    ("reduce_max", ""),
    ("consts['lpf']", ""),
])
def test_scope_of_an_op_name(op_name, scope):
    assert stage_trace.scope_of(op_name) == scope


def test_instruction_is_the_same_in_the_trace_and_the_program():
    event = ("%fusion.28 = (f32[1]{0:T(128)}, /*index=1*/f32[1]{0:T(128)}) "
             "fusion(f32[31]{0:T(128)} %consts__lpf__.1), kind=kLoop")
    line = ("  %fusion.28 = (f32[1]{0:T(128)}, /*index=1*/f32[1]{0:T(128)}) "
            "fusion(%consts__lpf__.1), kind=kLoop, calls=%fused.37, "
            'metadata={op_name="jit(run)/vmap(demod)/slice" '
            "stack_frame_id=49}")
    want = ("%fusion.28 = (f32[1]{0:T(128)}, /*index=1*/f32[1]{0:T(128)}) "
            "fusion")
    assert stage_trace.instruction(event) == want
    assert stage_trace.instruction(line) == want
    assert stage_trace.program_scopes([line]) == [{want: "demod"}]


# Two programs that share instruction names, as the B-mode and the
# Doppler program do: an execution is read against the program that
# holds most of the instructions it ran.
BMODE_HLO = """HloModule jit_run
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(run)/vmap(demod)/mul"}
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, metadata={op_name="jit(run)/vmap(beamform)/dot_general"}
  ROOT %x.1 = f32[4]{0} sqrt(%fusion.2), metadata={op_name="jit(run)/vmap(bmode)/sqrt"}
}"""
DOPPLER_HLO = BMODE_HLO.replace("sqrt(", "atan2(").replace(
    "vmap(bmode)/sqrt", "vmap(doppler)/atan2")


def ops(t0, head):
    return [["%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p)", t0, t0 + 100],
            ["%fusion.2 = f32[4]{0} fusion(f32[4]{0} %fusion.1)", t0 + 100,
             t0 + 300],
            [f"%x.1 = f32[4]{{0}} {head}(f32[4]{{0}} %fusion.2)", t0 + 300,
             t0 + 350],
            ["%copy.9 = f32[4]{0} copy(f32[4]{0} %x.1)", t0 + 350,
             t0 + 360]]


def test_each_execution_reads_its_own_program():
    execs = [["jit_run(7)", 0, 360], ["jit_run(8)", 400, 760]]
    scoped = stage_trace._scope_ops(
        ops(0, "sqrt") + ops(400, "atan2"), execs,
        stage_trace.program_scopes([BMODE_HLO, DOPPLER_HLO]))
    assert [s for s, _, _ in scoped] == [
        "demod", "beamform", "bmode", "", "demod", "beamform", "doppler", ""]


def hand_made(detect_twice=False, extra_execution=False):
    """Window 0..1000 ns (slice 100..900); batch 0 launched before the
    slice, batch 1 inside it."""
    plane = "/device:TPU:0"
    scopes = [["demod", 0, 300], ["beamform", 300, 500], ["bmode", 500, 550],
              ["", 550, 560], ["demod", 600, 800], ["beamform", 800, 1000]]
    executions = [["jit_run(7)", 0, 560], ["jit_run(7)", 600, 1000]]
    if extra_execution:
        executions.append(["jit_run(7)", 1000, 1010])
    spans = [["serve.launch", 0, "g", -20, -10],
             ["serve.poll", None, None, 100, 105],
             ["serve.launch", 1, "g", 150, 160],
             ["serve.detect", 0, "g", 565, 565],
             ["serve.detect", 1, "g", 1003, 1003]]
    if detect_twice:
        spans.append(["serve.detect", 1, "g", 1004, 1004])
    return {"markers": {trace.OPEN: [0], trace.LANDED: [560, 1000]},
            "scopes": {plane: scopes}, "executions": {plane: executions},
            "spans": spans}


def test_summary_of_a_hand_made_trace():
    s = stage_trace.summarize(hand_made())
    assert s["window_s"] == pytest.approx(800e-9)
    assert s["stage_s"] == pytest.approx(
        {"demod": 400e-9, "beamform": 300e-9, "bmode": 50e-9})
    assert s["unscoped_s"] == pytest.approx(10e-9)
    assert s["unmatched"] is None
    (b,) = s["batches"]                # batch 0 launched before the slice
    assert b["idx"] == 1 and b["group"] == "g"
    assert b["device_queue_s"] == pytest.approx(440e-9)   # 600 - 160
    assert b["detect_lag_s"] == pytest.approx(3e-9)       # 1003 - 1000


@pytest.mark.parametrize("name,value", [
    ("demod_ms_per_acq", 400 / 760 * 10.0),
    ("beamform_ms_per_acq", 300 / 760 * 10.0),
    ("head_ms_per_acq", 50 / 760 * 10.0),
    ("unscoped_ms_per_acq", 10 / 760 * 10.0),
    ("device_queue_p95_ms", 440e-6),
    ("detect_lag_p95_ms", 3e-6),
])
def test_layer_metrics_of_a_hand_made_trace(name, value):
    numbers = stage_trace.layer_metrics(
        stage_trace.summarize(hand_made()), busy_s=760e-9,
        device_ms_per_acq=10.0)
    assert numbers[name] == pytest.approx(value)


@pytest.mark.parametrize("kw,why", [
    ({"detect_twice": True}, "2 serve.detect"),
    ({"extra_execution": True}, "3 device executions for 2"),
])
def test_batches_that_do_not_match_one_to_one_give_none(kw, why):
    s = stage_trace.summarize(hand_made(**kw))
    assert s["batches"] is None and why in s["unmatched"]
    numbers = stage_trace.layer_metrics(s, busy_s=760e-9,
                                        device_ms_per_acq=10.0)
    assert "device_queue_p95_ms" not in numbers
    assert "detect_lag_p95_ms" not in numbers


def test_nothing_is_read_without_a_device_trace():
    s = stage_trace.summarize(dict(hand_made(), scopes={}, executions={}))
    assert s["stage_s"] == {} and s["batches"] is None
    assert stage_trace.layer_metrics(s, busy_s=0.0,
                                     device_ms_per_acq=None) == {}


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def test_recorded_window_splits_device_time_by_stage(recorded):
    base = trace.summarize(recorded)
    s = stage_trace.summarize(recorded)
    assert s["window_s"] == pytest.approx(base["window_s"])
    assert set(s["stage_s"]) == {"demod", "beamform", "bmode"}
    total = sum(s["stage_s"].values()) + s["unscoped_s"]
    assert total == pytest.approx(base["busy_s"], rel=0.01)
    assert s["unscoped_s"] < 0.05 * base["busy_s"]
    assert s["stage_s"]["demod"] > s["stage_s"]["beamform"] > (
        s["stage_s"]["bmode"])


def test_recorded_window_matches_every_batch(recorded):
    s = stage_trace.summarize(recorded)
    assert s["unmatched"] is None and s["batches"]
    launched = {sp[1] for sp in recorded["spans"]
                if sp[0] == "serve.launch"}
    assert len(launched) == len(
        next(iter(recorded["executions"].values())))
    for b in s["batches"]:
        assert 0 < b["detect_lag_s"] < 0.05
        assert -0.01 < b["device_queue_s"] < 0.2
