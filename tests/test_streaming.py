"""Streaming serve loop + distribution telemetry, and the serving CLI."""

import json
import os
import subprocess
import sys

import numpy as np

import jax.numpy as jnp

from repro.bench import bench_callable, bench_stages, latency_stats
from repro.core import tiny_config
from repro.data import synth_rf
from repro.core.aot import WarmPool
from repro.launch.scheduler import (BatchPolicy, StreamSpec, _make_frames,
                                    serve_multitenant)

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


def test_latency_stats_percentiles_and_misses():
    samples = [i / 1000.0 for i in range(1, 101)]      # 1..100 ms
    st = latency_stats(samples, budget_s=0.050)
    assert st.n == 100
    np.testing.assert_allclose(st.p50_s, 0.0505, atol=1e-6)
    assert st.p50_s <= st.p95_s <= st.p99_s
    np.testing.assert_allclose(st.jitter_s, st.p95_s - st.p50_s, atol=1e-12)
    assert st.miss_rate == 0.5                          # 51..100 ms late
    assert latency_stats(samples).miss_rate == 0.0      # no budget set


def test_bench_callable_records_distribution():
    res = bench_callable("t", lambda x: x * 2.0, (jnp.ones((8, 8)),),
                         input_bytes=1_000_000, warmup=1, runs=4,
                         deadline_s=100.0)
    assert len(res.samples_s) == 4
    assert res.stats is not None and res.stats.n == 4
    np.testing.assert_allclose(res.t_avg_s, np.mean(res.samples_s))
    assert res.stats.miss_rate == 0.0                   # generous budget


def test_ndjson_telemetry_schema():
    res = bench_callable("t", lambda x: x + 1.0, (jnp.ones((4, 4)),),
                         input_bytes=1000, warmup=1, runs=3, deadline_s=1.0)
    cfg = tiny_config()
    res.stage_breakdown = bench_stages(cfg, jnp.asarray(synth_rf(cfg)),
                                       runs=2)
    recs = [json.loads(line) for line in res.ndjson_lines()]
    kinds = [r["kind"] for r in recs]
    assert kinds[0] == "summary" and kinds.count("sample") == 3
    assert {r["stage"] for r in recs if r["kind"] == "stage"} == {
        "demod", "beamform", "bmode"}
    summary = recs[0]
    lat = summary["latency"]
    assert lat["p50_s"] <= lat["p95_s"] <= lat["p99_s"]
    for r in recs:
        if r["kind"] == "sample":
            assert r["deadline_missed"] is False


def test_acquisition_source_shapes_and_cycling():
    """A stream's RF pool: one acquisition per frame, distinct within
    the pool, cycling with period ``min(pool, n_frames)``."""
    cfg = tiny_config()
    spec = StreamSpec("probe0", cfg, n_frames=3, pool=2, seed=7)
    frames, dropped = _make_frames([spec])
    assert dropped == [0] and [f.seq for f in frames] == [0, 1, 2]
    a, b, c = (f.rf for f in frames)
    assert a.shape == cfg.rf_shape
    assert not np.array_equal(a, b)                     # distinct sweeps
    assert np.array_equal(a, c)                         # pool of 2 cycles
    assert spec.frame_seed(0) == spec.frame_seed(2) != spec.frame_seed(1)


def test_acquisition_sources_with_nearby_seeds_share_no_frame():
    """Disjoint per-stream seed spaces: an additive scheme
    (``seed + k``) makes two streams whose base seeds differ by less
    than ``pool`` serve byte-identical frames (seed 0's frame 2 was
    seed 2's frame 0). Hash-derived seed spaces must never collide
    across distinct base seeds."""
    cfg = tiny_config()
    frames_a = [f.rf for f in _make_frames(
        [StreamSpec("probe0", cfg, n_frames=4, pool=4, seed=0)])[0]]
    frames_b = [f.rf for f in _make_frames(
        [StreamSpec("probe0", cfg, n_frames=4, pool=4, seed=2)])[0]]
    for i, fa in enumerate(frames_a):
        for j, fb in enumerate(frames_b):
            assert not np.array_equal(fa, fb), (
                f"streams seed=0 frame {i} and seed=2 frame {j} are "
                f"byte-identical")
    # within one stream every pooled frame is still distinct
    for i, fa in enumerate(frames_a):
        for fb in frames_a[i + 1:]:
            assert not np.array_equal(fa, fb)


def test_streaming_batched_throughput_beats_single_frame():
    """Acceptance: sustained MB/s at batch N>1 >= single-frame MB/s.

    One backlog probe (every frame queued at once) through the serving
    loop at max_batch 8 / in_flight 2 against max_batch 1 / in_flight 1.
    At tiny geometry batching wins by a wide margin (dispatch overhead
    dominates), but this is still a wall-clock inequality on a shared
    machine — so it is asserted with the CI-exclusion rule over three
    windows per side (fail only when the whole ratio interval says the
    batched loop is slower). One warm pool serves every window, so
    only the first window of each shape compiles.
    """
    from repro.bench.stats import gate_ratio

    cfg = tiny_config()
    pool = WarmPool()

    def window(max_batch, in_flight):
        probe = StreamSpec("probe0", cfg, fps=1e9, n_frames=64,
                           deadline_ms=8000.0)
        return serve_multitenant(
            [probe], policy=BatchPolicy(max_batch=max_batch),
            in_flight=in_flight, pool=pool)

    single_mbps = [window(1, 1)["sustained_mbps"] for _ in range(3)]
    batched_mbps = [window(8, 2)["sustained_mbps"] for _ in range(3)]
    batched = window(8, 2)
    # higher_is_better at factor 1.0: fail only when the batched/single
    # ratio CI sits entirely below 1.0.
    dec = gate_ratio(single_mbps, batched_mbps, factor=1.0,
                     higher_is_better=True)
    assert dec.ok, dec.reason
    assert batched["acquisitions"] == 64
    assert batched["frames"] == 64 * cfg.n_f
    lat = batched["latency"]
    assert lat["p50_s"] <= lat["p95_s"] <= lat["p99_s"]
    probe = batched["per_stream"]["probe0"]
    assert probe["latency"]["budget_s"] == 8.0
    assert probe["deadline_miss_rate"] == 0.0


def _subprocess_env(tmp_path):
    """PYTHONPATH at the checkout's src; caches inside ``tmp_path``."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla-cache")
    env["REPRO_CONSTS_CACHE_DIR"] = str(tmp_path / "consts-cache")
    return env


def test_serve_cli_imports_no_language_model_code(tmp_path):
    """The ultrasound serving CLI loads nothing of the LM tree."""
    code = ("import sys, repro.launch.serve; "
            "print(sorted(m for m in sys.modules if m.startswith("
            "('repro.models', 'repro.configs', 'repro.train'))))")
    proc = subprocess.run([sys.executable, "-c", code],
                          env=_subprocess_env(tmp_path),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip() == "[]"


def test_serve_cli_single_probe_runs(tmp_path):
    """``--ultrasound --clients 1`` serves one probe through
    `serve_multitenant` and exits 0."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--ultrasound",
         "--clients", "1", "--frames", "6", "--max-batch", "2",
         "--deadline-ms", "1000"],
        env=_subprocess_env(tmp_path), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "multitenant/1streams/1groups/b2" in proc.stdout
    assert "6 acquisitions" in proc.stdout
    assert "probe0 [RF2IQ_DAS_BMODE/dynamic" in proc.stdout
