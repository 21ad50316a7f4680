"""Multi-tenant dynamic-batching scheduler: determinism oracle + policy.

The oracle is the PR's acceptance bar: every frame served through the
coalescing scheduler — batched with strangers, zero-padded to the policy
shape, dispatched in arrival order — must be BIT-IDENTICAL
(`np.array_equal`, not allclose) to the same frame run alone through
`monolithic_pipeline_fn`. Across all three variants and both
modalities: batching composition is an execution decision, and
execution decisions must never leak into pixels (paper §II-C).

Policy unit tests pin the scheduling invariants that no throughput
number can prove: a lone frame flushes once its queue delay reaches the
policy bound (it never waits forever for companions), occupancy never
exceeds ``max_batch``, eligible-head ties resolve deterministically,
and the idle loop's sleep horizon never admits a busy-spin.

The async in-flight tests re-run the oracle at dispatch-pipelining
depth >= 2 — including under an adversarial readiness gate that forces
completions to drain OUT of dispatch order — because overlap is an
execution decision too, and §II-C does not grant it an exemption.
"""

import collections
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Modality, Variant, tiny_config
from repro.core.executor import BatchedExecutor
from repro.core.staging import StagingRing
from repro.core.pipeline import init_pipeline, monolithic_pipeline_fn
from repro.data import synth_rf
from repro.launch.scheduler import (BatchPolicy, StreamSpec,
                                    make_mixed_streams, serve_multitenant)

BURST = 1e9          # arrival rate that lands every frame at t ~ 0


def _mono_oracle(cfg, rf):
    """One frame, alone, through the pre-stage-graph reference."""
    consts = jax.tree.map(jnp.asarray, init_pipeline(cfg))
    return np.asarray(jax.jit(monolithic_pipeline_fn(cfg))(
        consts, jnp.asarray(rf)))


@pytest.mark.parametrize("variant", [Variant.DYNAMIC, Variant.CNN,
                                     Variant.SPARSE])
def test_scheduler_output_bit_identical_to_monolithic_oracle(variant):
    """Coalesced multi-tenant serving changes no output bit.

    Two tenants (B-mode + Color Doppler) burst-arrive so the scheduler
    coalesces aggressively; max_batch=3 against 5/4 frames forces both
    full and partial (zero-padded) dispatches. Every served image must
    equal the per-frame monolithic reference exactly.
    """
    cfg_b = tiny_config(variant=variant)
    cfg_d = tiny_config(modality=Modality.DOPPLER, variant=variant)
    streams = [
        StreamSpec("b", cfg_b, fps=BURST, n_frames=5, seed=3, pool=5),
        StreamSpec("d", cfg_d, fps=BURST, n_frames=4, seed=11, pool=4),
    ]
    stats = serve_multitenant(
        streams, policy=BatchPolicy(max_batch=3, max_queue_delay_ms=2.0),
        collect_outputs=True)

    # modalities never share a compiled program; same-config tenants do
    assert len(stats["groups"]) == 2
    occ = stats["occupancy"]
    assert occ["frames"] == 9
    assert occ["max_occupancy"] <= 3
    assert occ["min_occupancy"] >= 1

    for sid, spec in (("b", streams[0]), ("d", streams[1])):
        outs = stats["outputs"][sid]
        assert len(outs) == spec.n_frames
        for k, out in enumerate(outs):
            rf = synth_rf(spec.cfg, seed=spec.frame_seed(k))
            want = _mono_oracle(spec.cfg, rf)
            assert out.dtype == want.dtype and out.shape == want.shape
            assert np.array_equal(out, want), (
                f"{sid}[{k}] ({variant.value}) drifted from the "
                f"monolithic oracle: max|d|="
                f"{np.abs(out - want).max()}")


@pytest.mark.parametrize("variant", [Variant.DYNAMIC, Variant.CNN])
@pytest.mark.parametrize("in_flight", [2, 3])
def test_async_in_flight_oracle_bit_identical(variant, in_flight):
    """Pipelined dispatch (depth >= 2) changes no output bit.

    Same two-tenant burst as the synchronous oracle test, but with the
    in-flight ring enabled: batches launch while earlier ones are still
    computing, and completions drain via non-blocking readiness checks.
    Every served image must still equal the per-frame monolithic
    reference exactly, and the ring telemetry must respect the bound.
    """
    cfg_b = tiny_config(variant=variant)
    cfg_d = tiny_config(modality=Modality.DOPPLER, variant=variant)
    streams = [
        StreamSpec("b", cfg_b, fps=BURST, n_frames=5, seed=3, pool=5),
        StreamSpec("d", cfg_d, fps=BURST, n_frames=4, seed=11, pool=4),
    ]
    stats = serve_multitenant(
        streams, policy=BatchPolicy(max_batch=3, max_queue_delay_ms=2.0),
        in_flight=in_flight, collect_outputs=True)

    ifo = stats["in_flight_occupancy"]
    assert stats["in_flight"] == in_flight
    assert 1 <= ifo["max_depth"] <= in_flight
    # Host transfer telemetry stays a share of the wall; device time is
    # the profiler's (no host-poll busy clock in the stats).
    assert 0.0 < stats["h2d_s"] <= stats["transfer_frac"] * stats["wall_s"]
    assert not {"device_busy_frac", "overlap_frac"} & set(stats)
    assert stats["warmup_s"] > 0.0      # AOT compile measured, not hidden

    for sid, spec in (("b", streams[0]), ("d", streams[1])):
        outs = stats["outputs"][sid]
        assert len(outs) == spec.n_frames
        for k, out in enumerate(outs):
            rf = synth_rf(spec.cfg, seed=spec.frame_seed(k))
            want = _mono_oracle(spec.cfg, rf)
            assert np.array_equal(out, want), (
                f"{sid}[{k}] ({variant.value}, in_flight={in_flight}) "
                f"drifted from the monolithic oracle")


def test_out_of_order_drain_bit_identical(monkeypatch):
    """Cross-group out-of-order completion drains leave no pixel trace.

    An adversarial readiness gate holds back the FIRST launched batch
    until a later-launched batch (necessarily of the other group) has
    retired — forcing the drain order to differ from the dispatch
    order. Outputs are keyed by (stream, seq), so the oracle must still
    hold bit-for-bit; the gate also records the retire order so the
    test can prove the adversarial schedule actually happened.
    """
    import repro.launch.scheduler as sched

    real_ready = sched._ready
    launch_order = {}           # id(out) -> launch index (first-seen)
    keep = []                   # pin outs so ids can't be recycled
    retire_order = []
    refusals = {"n": 0}

    def gate(out):
        key = id(out)
        if key not in launch_order:
            launch_order[key] = len(launch_order)
            keep.append(out)
        # Hold the first launch until a later one retires (liveness
        # valve: give up the adversary after enough refusals so a
        # pathological timing can never deadlock the test).
        if (launch_order[key] == 0 and not retire_order
                and refusals["n"] < 5000):
            refusals["n"] += 1
            return False
        if not real_ready(out):
            return False
        retire_order.append(launch_order[key])
        return True

    monkeypatch.setattr(sched, "_ready", gate)

    cfg_b = tiny_config(variant=Variant.DYNAMIC)
    cfg_d = tiny_config(modality=Modality.DOPPLER, variant=Variant.DYNAMIC)
    streams = [
        StreamSpec("b", cfg_b, fps=BURST, n_frames=5, seed=3, pool=5),
        StreamSpec("d", cfg_d, fps=BURST, n_frames=4, seed=11, pool=4),
    ]
    stats = serve_multitenant(
        streams, policy=BatchPolicy(max_batch=3, max_queue_delay_ms=2.0),
        in_flight=3, collect_outputs=True)

    # The adversarial schedule really ran: something retired before the
    # first launch did.
    assert retire_order[0] != 0, retire_order
    assert sorted(retire_order) == list(range(len(launch_order)))

    for sid, spec in (("b", streams[0]), ("d", streams[1])):
        for k, out in enumerate(stats["outputs"][sid]):
            rf = synth_rf(spec.cfg, seed=spec.frame_seed(k))
            assert np.array_equal(out, _mono_oracle(spec.cfg, rf)), (
                f"{sid}[{k}] drifted under out-of-order drains")


def test_in_flight_one_recovers_synchronous_loop():
    """Depth 1: the ring holds one slot, so every launch retires before
    the next — depth telemetry must be exactly 1 everywhere."""
    cfg = tiny_config(variant=Variant.DYNAMIC)
    stats = serve_multitenant(
        [StreamSpec("s", cfg, fps=BURST, n_frames=6)],
        policy=BatchPolicy(max_batch=2, max_queue_delay_ms=0.0),
        in_flight=1)
    ifo = stats["in_flight_occupancy"]
    assert ifo["max_depth"] == 1 and ifo["mean_depth"] == 1.0
    assert ifo["full_rate"] == 1.0
    with pytest.raises(ValueError, match="in_flight"):
        serve_multitenant([StreamSpec("s", cfg)], in_flight=0)


def test_pick_group_tie_break_is_stable_construction_order():
    """Two eligible heads with IDENTICAL arrival times: the first group
    in construction (= spec) order wins, and reversing the list flips
    the winner — the tie is broken by order, not by dict/hash
    accident, so a rerun with identical arrivals replays identical
    dispatch order."""
    from repro.launch.scheduler import _Frame, _Group, _pick_group

    policy = BatchPolicy(max_batch=4, max_queue_delay_ms=5.0)
    a = _Group("a", None, None)
    b = _Group("b", None, None)
    t = 1.000
    a.queue.append(_Frame(stream=0, seq=0, rf=None, t_arrival=t))
    b.queue.append(_Frame(stream=1, seq=0, rf=None, t_arrival=t))

    now = t + 0.006                     # both heads past the delay bound
    assert _pick_group([a, b], now, policy) is a
    assert _pick_group([b, a], now, policy) is b


def test_idle_horizon_never_busy_spins():
    """Whenever the idle horizon is <= now, progress is already due —
    an arrival to admit or an expired head `_pick_group` will flush —
    so the serving loop's `dt <= 0` branch can never spin without
    work. Future-only state yields a strictly positive horizon gap."""
    from repro.launch.scheduler import (_Frame, _Group, _idle_horizon,
                                        _pick_group)

    policy = BatchPolicy(max_batch=4, max_queue_delay_ms=5.0)
    delay_s = policy.max_queue_delay_ms / 1e3

    # Case 1: queue head past the delay bound -> horizon expired AND
    # _pick_group immediately offers that group.
    g = _Group("g", None, None)
    g.queue.append(_Frame(stream=0, seq=0, rf=None, t_arrival=1.0))
    now = 1.0 + delay_s + 0.001
    hz = _idle_horizon([], 0, [g], delay_s)
    assert hz is not None and hz <= now
    assert _pick_group([g], now, policy) is g

    # Case 2: un-admitted arrival in the past -> horizon expired AND the
    # admission sweep (frames[ai].t_arrival <= now) is already due.
    frames = [_Frame(stream=0, seq=0, rf=None, t_arrival=2.0)]
    hz = _idle_horizon(frames, 0, [_Group("e", None, None)], delay_s)
    assert hz == 2.0
    assert hz <= 2.5                    # due at any now >= arrival

    # Case 3: only future events -> strictly positive gap (the loop
    # sleeps, never spins); no events at all -> no horizon.
    now = 1.0
    frames = [_Frame(stream=0, seq=0, rf=None, t_arrival=1.5)]
    g2 = _Group("g2", None, None)
    g2.queue.append(_Frame(stream=0, seq=0, rf=None, t_arrival=now))
    hz = _idle_horizon(frames, 0, [g2], delay_s)
    assert hz is not None and hz > now
    assert _idle_horizon([], 0, [_Group("x", None, None)],
                         delay_s) is None


def test_deadline_miss_count_is_exact():
    """Misses are counted per frame, not reconstructed from the rounded
    miss_rate float: an impossible budget misses every frame of the
    budgeted stream and only those."""
    cfg = tiny_config(variant=Variant.DYNAMIC)
    streams = [
        StreamSpec("tight", cfg, fps=BURST, n_frames=3,
                   deadline_ms=1e-9),          # unmeetable -> all miss
        StreamSpec("free", cfg, fps=BURST, n_frames=2,
                   deadline_ms=None),          # unbudgeted -> excluded
    ]
    stats = serve_multitenant(
        streams, policy=BatchPolicy(max_batch=2, max_queue_delay_ms=0.0))
    assert stats["per_stream"]["tight"]["deadline_miss_rate"] == 1.0
    # Aggregate rate counts budgeted frames only: 3 misses / 3 budgeted.
    assert stats["deadline_miss_rate"] == 1.0


def test_lone_frame_flushes_at_deadline_never_waits_forever():
    """A batch that will never fill must flush at max_queue_delay."""
    cfg = tiny_config(variant=Variant.DYNAMIC)
    stats = serve_multitenant(
        [StreamSpec("solo", cfg, fps=BURST, n_frames=1)],
        policy=BatchPolicy(max_batch=8, max_queue_delay_ms=50.0))
    qd = stats["queue_delay"]
    # The flush trigger is the policy bound, not a full batch: the one
    # frame waited at least 50 ms — and the window terminated, which is
    # the "never waits forever" half of the invariant.
    assert qd["n"] == 1
    assert 0.05 <= qd["p50_s"] < 5.0
    assert stats["occupancy"]["batches"] == 1
    assert stats["occupancy"]["max_occupancy"] == 1
    assert stats["acquisitions"] == 1


def test_occupancy_never_exceeds_max_batch():
    """A 10-frame burst under max_batch=4 dispatches as 4+4+2."""
    cfg = tiny_config(variant=Variant.DYNAMIC)
    stats = serve_multitenant(
        [StreamSpec("burst", cfg, fps=BURST, n_frames=10)],
        policy=BatchPolicy(max_batch=4, max_queue_delay_ms=0.0))
    occ = stats["occupancy"]
    assert occ["max_occupancy"] <= 4
    assert occ["frames"] == 10
    assert occ["batches"] == 3          # 4 + 4 + 2, FIFO
    (group,) = stats["groups"].values()
    assert group["batches"] == 3


def test_auto_variant_groups_with_explicit_twin():
    """An AUTO tenant resolves through the planner and shares the
    compiled program of an explicitly-configured twin."""
    cfg = tiny_config(variant=Variant.DYNAMIC)        # cpu heuristic pick
    auto = tiny_config(variant=Variant.AUTO)
    stats = serve_multitenant(
        [StreamSpec("explicit", cfg, fps=BURST, n_frames=2),
         StreamSpec("auto", auto, fps=BURST, n_frames=2)],
        policy=BatchPolicy(max_batch=4, max_queue_delay_ms=1.0),
        plan_policy="heuristic")
    (group,) = stats["groups"].values()
    assert sorted(group["streams"]) == ["auto", "explicit"]
    assert group["plan"]["variant"] == "dynamic"


def test_per_stream_deadlines_and_telemetry_shape():
    """Per-stream budgets produce per-stream miss rates; the record
    passes the shared NDJSON schema."""
    from repro.bench.schema import validate_record

    cfg = tiny_config(variant=Variant.DYNAMIC)
    streams = make_mixed_streams(
        2, cfg, cfg.with_(modality=Modality.DOPPLER),
        base_fps=200.0, n_frames=4, deadline_ms=1e6)   # un-missable
    stats = serve_multitenant(
        streams, policy=BatchPolicy(max_batch=2, max_queue_delay_ms=2.0))
    assert stats["deadline_miss_rate"] == 0.0
    for s in stats["per_stream"].values():
        assert s["deadline_miss_rate"] == 0.0
        assert s["latency"]["n"] == 4
    validate_record({"kind": "multitenant", **stats})


def test_frame_pool_cycles_with_period_min_pool_n_frames():
    """The documented pool contract, pinned: frame RF cycles with
    period ``min(pool, n_frames)`` (never more pools than frames are
    synthesized — a 5-pool 3-frame stream has 3 distinct frames, not a
    phantom 5), and seeds within one period are distinct."""
    cfg = tiny_config()

    short = StreamSpec("s", cfg, n_frames=3, pool=5, seed=9)
    assert min(short.pool, short.n_frames) == 3
    assert len({short.frame_seed(k) for k in range(3)}) == 3

    long = StreamSpec("s", cfg, n_frames=10, pool=4, seed=9)
    assert long.frame_seed(4) == long.frame_seed(0)    # period 4
    assert long.frame_seed(9) == long.frame_seed(1)
    assert len({long.frame_seed(k) for k in range(4)}) == 4

    # Same (seed, stream_id, slot) -> same seed regardless of how the
    # period was reached: the pool bound changes WHICH slots exist,
    # never what a slot contains.
    assert short.frame_seed(0) == StreamSpec(
        "s", cfg, n_frames=8, pool=8, seed=9).frame_seed(0)


def test_streams_with_adjacent_seeds_share_no_frame():
    """Disjoint per-stream seed spaces: under the old additive scheme
    (``seed + i``) two tenants whose base seeds differ by less than the
    pool span served byte-identical RF (seed 0 frame 1 == seed 1 frame
    0). `frame_seed` hashes (seed, stream_id), so neither adjacent base
    seeds nor equal ones may collide across distinct streams."""
    cfg = tiny_config()
    a = StreamSpec("a", cfg, n_frames=4, pool=4, seed=0)
    b = StreamSpec("b", cfg, n_frames=4, pool=4, seed=1)   # adjacent
    c = StreamSpec("c", cfg, n_frames=4, pool=4, seed=0)   # equal
    pools = {s.stream_id: [synth_rf(cfg, seed=s.frame_seed(k))
                           for k in range(4)] for s in (a, b, c)}
    for x, y in (("a", "b"), ("a", "c"), ("b", "c")):
        for i, fx in enumerate(pools[x]):
            for j, fy in enumerate(pools[y]):
                assert not np.array_equal(fx, fy), (
                    f"streams {x}[{i}] and {y}[{j}] share a "
                    f"byte-identical frame")


def test_policy_and_spec_validation():
    cfg = tiny_config()
    with pytest.raises(ValueError):
        BatchPolicy(max_batch=0)
    with pytest.raises(ValueError):
        BatchPolicy(max_batch=4, max_queue_delay_ms=-1.0)
    with pytest.raises(ValueError):
        StreamSpec("s", cfg, fps=0.0)
    with pytest.raises(ValueError):
        StreamSpec("s", cfg, n_frames=0)
    with pytest.raises(ValueError):
        serve_multitenant([])
    with pytest.raises(ValueError, match="duplicate"):
        serve_multitenant([StreamSpec("x", cfg), StreamSpec("x", cfg)])


def test_saturated_tenant_cannot_starve_sparse_tenants_frame():
    """Oldest eligible head wins (pure flush-policy logic, no timing):
    a tenant whose queue is ALWAYS full must not keep winning the flush
    over another tenant's expired older frame — that would push the
    sparse tenant's queue delay unboundedly past max_queue_delay_ms."""
    from repro.launch.scheduler import _Frame, _Group, _pick_group

    def frame(t):
        return _Frame(stream=0, seq=0, rf=None, t_arrival=t)

    policy = BatchPolicy(max_batch=4, max_queue_delay_ms=5.0)
    hog = _Group("hog", None, None)
    hog.queue.extend(frame(1.000 + i * 1e-4) for i in range(8))  # full
    solo = _Group("solo", None, None)
    solo.queue.append(frame(0.999))                  # older, not full

    # solo's head not yet expired -> the full queue flushes
    assert _pick_group([hog, solo], now=1.001, policy=policy) is hog
    # solo's head expired and OLDER than the full queue's -> solo wins,
    # no matter how full hog is (full-queue-first starved it here)
    assert _pick_group([hog, solo], now=1.005, policy=policy) is solo
    # once solo drained, hog flushes again; nothing pending -> None
    solo.queue.clear()
    assert _pick_group([hog, solo], now=1.005, policy=policy) is hog
    hog.queue.clear()
    assert _pick_group([hog, solo], now=1.005, policy=policy) is None


def _staged(eng, frames, pad_to):
    """One batch launched as the serving loop launches it: staged into
    a ring slot (pad rows zero), `place`, `dispatch_staged`; returns
    the valid rows."""
    ring = StagingRing(pad_to, eng.cfg.rf_shape, eng.cfg.rf_dtype, depth=1)
    buf, b = ring.stage(frames)
    return np.asarray(eng.dispatch_staged(eng.place(buf), pad_to))[:b]


def test_sharded_call_padded_degenerate_single_device_mesh():
    """ShardedExecutor's place + dispatch_staged on the 1-device mesh:
    same contract as the batched path (the true multi-device run is the
    subprocess test below, same pattern as test_sharded_executor.py)."""
    from repro.core.executor import ShardedExecutor

    cfg = tiny_config(variant=Variant.DYNAMIC)
    eng = ShardedExecutor(cfg)        # all local devices (1 in-process)
    if eng.n_devices != 1:            # pragma: no cover - env-dependent
        pytest.skip("main process must see a single device")
    rf = [synth_rf(cfg, seed=s) for s in range(2)]
    out = _staged(eng, rf, 4)
    assert out.shape[0] == 2
    assert np.array_equal(out, np.asarray(eng(jnp.asarray(np.stack(rf)))))
    with pytest.raises(ValueError, match="exceeds pad_to"):
        _staged(eng, rf, 1)


def test_call_padded_fixed_shape_contract():
    """The heterogeneous-arrival launch: any occupancy 1..pad_to
    returns exactly the valid rows (pad rows change nothing), over- or
    empty batches are refused, and the AOT program takes only its own
    padded shape."""
    from repro.core.aot import aot_warm

    cfg = tiny_config(variant=Variant.DYNAMIC)
    eng = BatchedExecutor(cfg)
    rf3 = [synth_rf(cfg, seed=s) for s in range(3)]
    full = np.asarray(eng(jnp.asarray(np.stack(rf3))))
    padded = _staged(eng, rf3, 4)
    assert padded.shape == full.shape
    assert np.array_equal(padded, full)
    one = _staged(eng, rf3[:1], 4)
    assert np.array_equal(one[0], full[0])
    with pytest.raises(ValueError, match="exceeds pad_to"):
        _staged(eng, rf3, 2)
    with pytest.raises(ValueError, match="empty"):
        _staged(eng, [], 4)
    aot_warm(eng, 2)
    with pytest.raises(TypeError, match="compiled with"):
        eng.dispatch_staged(eng.place(np.stack(rf3)), 2)


# ---------------------------------------------------------------------------
# Subprocess: sharded multi-tenant dispatch on a forced 2-device CPU mesh
# (XLA locks the host device count at first jax init — same pattern as
# tests/test_sharded_executor.py)
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import BatchedExecutor, Modality, ShardedExecutor, \
    Variant, tiny_config
from repro.core.pipeline import init_pipeline, monolithic_pipeline_fn
from repro.core.staging import StagingRing
from repro.data import synth_rf
from repro.launch.scheduler import BatchPolicy, StreamSpec, \
    serve_multitenant

out = {"device_count": jax.device_count()}
cfg = tiny_config(variant=Variant.DYNAMIC)


def staged(eng, frames, pad_to):
    ring = StagingRing(pad_to, cfg.rf_shape, cfg.rf_dtype, depth=1)
    buf, b = ring.stage(frames)
    return np.asarray(eng.dispatch_staged(eng.place(buf), pad_to))[:b]


# place + dispatch_staged: fixed SPMD shape, valid rows match the
# batched oracle
eng = ShardedExecutor(cfg)
oracle = BatchedExecutor(cfg)
errs = {}
for B in (1, 3, 4):
    rf = [synth_rf(cfg, seed=i) for i in range(B)]
    got = staged(eng, rf, 4)
    want = np.asarray(oracle(jnp.asarray(np.stack(rf))))
    errs[str(B)] = [list(got.shape) == list(want.shape),
                    float(np.abs(got - want).max())]
out["errs"] = errs
try:
    staged(eng, [synth_rf(cfg, seed=0)], 3)
    out["pad_to_odd_raised"] = False
except ValueError:
    out["pad_to_odd_raised"] = True

# sharded multi-tenant window: plan stamps carry the mesh, outputs
# match the per-frame monolithic oracle
cfg_d = tiny_config(modality=Modality.DOPPLER, variant=Variant.DYNAMIC)
streams = [StreamSpec("b", cfg, fps=1e9, n_frames=3, pool=3),
           StreamSpec("d", cfg_d, fps=1e9, n_frames=2, pool=2)]
stats = serve_multitenant(
    streams, policy=BatchPolicy(max_batch=2, max_queue_delay_ms=2.0),
    devices=jax.local_devices(), collect_outputs=True)
max_err = 0.0
for sid, spec in (("b", streams[0]), ("d", streams[1])):
    consts = jax.tree.map(jnp.asarray, init_pipeline(spec.cfg))
    mono = jax.jit(monolithic_pipeline_fn(spec.cfg))
    for k, img in enumerate(stats["outputs"][sid]):
        want = np.asarray(mono(consts, jnp.asarray(
            synth_rf(spec.cfg, seed=spec.frame_seed(k)))))
        max_err = max(max_err, float(np.abs(img - want).max()))
out["mt_max_err"] = max_err
out["mt_plan_devices"] = [g["plan"]["devices"]
                          for g in stats["groups"].values()]
out["mt_occ_max"] = stats["occupancy"]["max_occupancy"]
out["mt_acqs"] = stats["acquisitions"]
try:
    serve_multitenant(streams, policy=BatchPolicy(max_batch=3),
                      devices=jax.local_devices())
    out["odd_max_batch_raised"] = False
except ValueError:
    out["odd_max_batch_raised"] = True
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_results(tmp_path_factory):
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    # Sandbox the subprocess's persistent compile cache like conftest
    # does in-process (AOT warm-up must not touch the user cache dir).
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("subproc-xla-cache"))
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_call_padded_matches_oracle(sharded_results):
    assert sharded_results["device_count"] == 2
    for b, (shape_ok, err) in sharded_results["errs"].items():
        assert shape_ok, f"batch {b}: shape mismatch"
        assert err < 1e-5, f"batch {b}: max abs err {err}"
    # pad_to must split evenly across the mesh
    assert sharded_results["pad_to_odd_raised"] is True


def test_sharded_multitenant_window(sharded_results):
    """The scheduler's sharded dispatch path: every served frame
    allclose to the monolithic oracle, plan stamps name the mesh,
    policy invariants hold, and an indivisible max_batch is refused."""
    r = sharded_results
    assert r["mt_max_err"] < 1e-5
    assert r["mt_plan_devices"] == [2, 2]
    assert r["mt_occ_max"] <= 2
    assert r["mt_acqs"] == 5
    assert r["odd_max_batch_raised"] is True


def test_drain_block_mode_bit_identical_and_validated():
    """drain='block' keeps the legacy detect-block-harvest retirement:
    same pixels, same schema stamps, only the transfer timing moves.
    Invalid modes are refused before any work happens."""
    cfg_b = tiny_config(variant=Variant.DYNAMIC)
    cfg_d = tiny_config(modality=Modality.DOPPLER,
                        variant=Variant.DYNAMIC)
    streams = [
        StreamSpec("b", cfg_b, fps=BURST, n_frames=5, seed=3, pool=5),
        StreamSpec("d", cfg_d, fps=BURST, n_frames=4, seed=11, pool=4),
    ]
    policy = BatchPolicy(max_batch=3, max_queue_delay_ms=2.0)
    blocked = serve_multitenant(streams, policy=policy, in_flight=2,
                                drain="block", collect_outputs=True)
    asynced = serve_multitenant(streams, policy=policy, in_flight=2,
                                drain="async", collect_outputs=True)
    assert blocked["drain"] == "block" and asynced["drain"] == "async"
    assert blocked["name"].count("/block/") == 1
    assert asynced["name"].count("/async/") == 1
    for sid in ("b", "d"):
        for a, b in zip(asynced["outputs"][sid], blocked["outputs"][sid]):
            assert np.array_equal(a, b)    # drain mode never moves bits

    with pytest.raises(ValueError, match="drain must be"):
        serve_multitenant(streams, policy=policy, drain="sideways")


def test_adaptive_poll_grain_bounded_by_horizon_and_cap():
    """The busy-poll sleep stretches toward the next arrival horizon
    but never past the completion-detection cap, never below the base
    grain, and falls back to the base when no horizon exists."""
    from repro.launch.scheduler import (_POLL_CAP_S, _POLL_S,
                                        _poll_base, _poll_grain)

    base = 2e-4
    # No horizon (all arrivals admitted): base grain.
    assert _poll_grain(1.0, None, base=base) == base
    # Distant horizon: capped at the detection bound.
    assert _poll_grain(1.0, 10.0, base=base) == _POLL_CAP_S
    # Near horizon: sleep exactly to it.
    assert _poll_grain(1.0, 1.0 + 1e-3, base=base) == pytest.approx(1e-3)
    # Past/immediate horizon: never below the base grain.
    assert _poll_grain(1.0, 0.5, base=base) == base
    assert _POLL_S <= _POLL_CAP_S


def test_poll_base_env_override(monkeypatch):
    from repro.launch import scheduler

    monkeypatch.delenv("REPRO_POLL_S", raising=False)
    assert scheduler._poll_base() == scheduler._POLL_S
    monkeypatch.setenv("REPRO_POLL_S", "0.002")
    assert scheduler._poll_base() == pytest.approx(0.002)
    # Invalid or non-positive overrides fall back, never crash.
    monkeypatch.setenv("REPRO_POLL_S", "banana")
    assert scheduler._poll_base() == scheduler._POLL_S
    monkeypatch.setenv("REPRO_POLL_S", "-1")
    assert scheduler._poll_base() == scheduler._POLL_S


SERVE_STEPS = ("serve.stage", "serve.place", "serve.launch", "serve.detect",
               "serve.harvest")


def _serve_spans(log_dir):
    """(start_ns, name, annotation args) of every ``serve.*`` span the
    profiler recorded under ``log_dir``, in time order."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(path)
    return sorted(((e.start_ns, e.name, dict(e.stats))
                   for plane in data.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("serve.")),
                  key=lambda span: span[0])


@pytest.mark.parametrize("drain", ["async", "block"])
def test_serving_loop_spans_each_batch_in_order(tmp_path, drain):
    """Under the profiler every launched batch leaves exactly one stage,
    place, launch, detect and harvest span, in that order, each carrying
    the batch's launch index and group key; the loop's other spans
    (poll, idle) carry neither. Tracing changes no scheduling decision
    and no output."""
    streams = [
        StreamSpec("b", tiny_config(), fps=BURST, n_frames=5, seed=3,
                   pool=5),
        StreamSpec("d", tiny_config(modality=Modality.DOPPLER), fps=BURST,
                   n_frames=4, seed=11, pool=4),
    ]

    def window():
        return serve_multitenant(
            streams, policy=BatchPolicy(max_batch=3, max_queue_delay_ms=2.0),
            in_flight=2, drain=drain, collect_outputs=True)

    plain = window()
    with jax.profiler.trace(str(tmp_path)):
        traced = window()

    assert traced["dispatch_order"] == plain["dispatch_order"]
    for sid, outs in plain["outputs"].items():
        assert len(traced["outputs"][sid]) == len(outs)
        for a, b in zip(outs, traced["outputs"][sid]):
            assert np.array_equal(a, b)

    per_batch = collections.defaultdict(list)
    for _, name, args in _serve_spans(str(tmp_path)):
        if "idx" in args:
            per_batch[args["idx"]].append((name, args["group"]))
        else:
            assert name in ("serve.poll", "serve.idle"), name
    group_of = {sid: key for key, g in traced["groups"].items()
                for sid in g["streams"]}
    assert sorted(per_batch) == list(range(len(traced["dispatch_order"])))
    for idx, batch in enumerate(traced["dispatch_order"]):
        assert [name for name, _ in per_batch[idx]] == list(SERVE_STEPS)
        assert {group for _, group in per_batch[idx]} == {
            group_of[batch[0][0]]}
