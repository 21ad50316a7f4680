"""Resource metering: peak-memory high-water mark, energy gating, stamping.

The CPU stand-in exercises the `live_arrays` fallback and the
NVML-unavailable path (`energy_joules is None`, never a crash) — the
GPU allocator path is covered structurally via an injected fake.
"""

import json

import numpy as np

import jax
import jax.numpy as jnp

from repro.bench import (NvmlEnergyMeter, ResourceMeter, ResourceStats,
                         bench_callable)
from repro.bench.resources import device_peak_memory_bytes, live_array_bytes
from repro.core import tiny_config
from repro.launch.scheduler import BatchPolicy, StreamSpec, serve_multitenant


def test_resource_stats_json_nulls_distinguish_unmeasured():
    st = ResourceStats()
    d = st.json_dict()
    assert d["peak_memory_bytes"] is None
    assert d["energy_joules"] is None
    assert d["memory_source"] is None
    assert json.loads(json.dumps(d)) == d       # JSON-serializable


def test_peak_memory_monotone_under_allocation():
    """The high-water mark grows with live allocations and never shrinks."""
    meter = ResourceMeter()
    meter.start()
    small = jnp.ones((64,), jnp.float32)
    jax.block_until_ready(small)
    meter.sample()
    peak_small = meter._peak
    big = jnp.ones((1_000_000,), jnp.float32)   # +4 MB live
    jax.block_until_ready(big)
    meter.sample()
    peak_big = meter._peak
    assert peak_big >= peak_small + 4_000_000 * 0.9
    del big
    meter.sample()                               # freeing never lowers peak
    st = meter.stop()
    assert st.peak_memory_bytes == peak_big
    assert st.memory_source == "live_arrays"     # CPU: no allocator stats
    assert st.devices == len(jax.local_devices())
    assert st.duration_s is not None and st.duration_s >= 0
    del small


def test_cpu_has_no_allocator_stats_but_live_arrays_counts():
    devs = jax.local_devices()
    assert device_peak_memory_bytes(devs) is None
    keep = jnp.ones((1024,), jnp.float32)
    jax.block_until_ready(keep)
    assert live_array_bytes(devs) >= keep.nbytes
    del keep


def test_allocator_peak_is_window_scoped(monkeypatch):
    """A process-lifetime allocator peak inherited from an earlier run
    must not be reported as this window's peak (falls back to sampled
    bytes_in_use); a new high-water mark set inside the window is."""
    from repro.bench import resources as res_lib

    readings = iter([
        [(5000, 5000)],   # start() baseline: lifetime peak 5000
        [(5000, 400)],    # sample 1: old peak stands -> report in_use 400
        [(5000, 900)],    # sample 2: still the old peak -> in_use 900
        [(7000, 6500)],   # sample 3: new high-water mark inside window
    ])
    monkeypatch.setattr(res_lib, "device_memory_stats_list",
                        lambda devices: next(readings))
    meter = res_lib.ResourceMeter(devices=jax.local_devices())
    meter.start()                    # consumes baseline + first sample
    assert meter._peak == 400
    assert meter._source == "device_bytes_in_use"
    meter.sample()
    assert meter._peak == 900
    meter.sample()
    assert meter._peak == 7000
    assert meter._source == "device_memory_stats"


def test_allocator_window_scoping_is_per_device(monkeypatch):
    """Device 0's huge pre-window lifetime peak must not be attributed
    to the window just because device 1 set a new (small) peak — the
    baseline comparison is per device, never on the sums."""
    from repro.bench import resources as res_lib

    readings = iter([
        [(10_000, 100), (500, 100)],   # baseline: dev0 has an old 10k peak
        [(10_000, 200), (800, 700)],   # window: dev1 peaks at 800, dev0 idle
    ])
    monkeypatch.setattr(res_lib, "device_memory_stats_list",
                        lambda devices: next(readings))
    meter = res_lib.ResourceMeter(devices=jax.local_devices())
    meter.start()
    assert meter._peak == 200 + 800                # not 10_000 + 800
    assert meter._source == "device_bytes_in_use"  # mixed -> lower bound


def test_energy_meter_none_off_gpu():
    """No pynvml / no GPU: available() False, stop() returns None cleanly."""
    meter = NvmlEnergyMeter()
    assert meter.available() is False
    meter.start()                                # must not raise
    assert meter.stop() is None
    st = ResourceMeter().stop()                  # stop without start: no crash
    assert st.energy_joules is None and st.energy_source is None


def test_energy_poll_integrates_tail_of_short_windows():
    """Even a window shorter than poll_s integrates at least the
    start->stop interval — a measured window never reports 0.0 J merely
    because no poll tick fired inside it."""
    class FakePower(NvmlEnergyMeter):
        def __init__(self):
            super().__init__(poll_s=60.0)        # no tick fires in-window
            self._handles = [object()]           # force available()
            self._calls = 0

        def _power_w(self):
            self._calls += 1
            return 10.0 if self._calls == 1 else 50.0   # idle 10W, then 50W

    meter = FakePower()
    assert meter.available()
    meter.start()
    import time
    time.sleep(0.02)
    joules = meter.stop()
    assert joules is not None and joules > 0.0   # 40W above idle, >0 s


def test_energy_none_when_every_power_read_fails():
    """Handles exist but power queries fail: None, never a fake 0.0 J."""
    class DeadPower(NvmlEnergyMeter):
        def __init__(self):
            super().__init__(poll_s=0.01)
            self._handles = [object()]

        def _power_w(self):
            return None                      # NVML_ERROR_NOT_SUPPORTED

    meter = DeadPower()
    assert meter.available()
    meter.start()                            # idle read fails -> no thread
    assert meter.stop() is None


def test_nvml_index_mapping_respects_visible_devices():
    from repro.bench.resources import nvml_indices_for_local_gpus as f
    assert f([0, 1], visible=None) == [0, 1]          # all boards visible
    assert f([0, 1], visible="2,3") == [2, 3]         # pinned job remaps
    assert f([1], visible="3,1,0") == [1]
    assert f([0], visible="GPU-aaaa-bbbb") is None    # UUID: unmappable
    assert f([2], visible="0,1") is None              # out of range


def test_injected_energy_meter_is_reported():
    class Fake:
        def available(self):
            return True

        def start(self):
            pass

        def stop(self):
            return 42.5

    meter = ResourceMeter(energy_meter=Fake())
    meter.start()
    st = meter.stop()
    assert st.energy_joules == 42.5
    assert st.energy_source == "nvml"


def test_bench_callable_stamps_resources_into_ndjson():
    res = bench_callable("t", lambda x: x * 2.0, (jnp.ones((32, 32)),),
                         input_bytes=1000, warmup=1, runs=3)
    assert res.resources is not None
    assert res.resources["energy_joules"] is None
    assert res.resources["peak_memory_bytes"] is not None
    recs = [json.loads(line) for line in res.ndjson_lines()]
    summary = recs[0]
    assert summary["kind"] == "summary"
    assert summary["resources"]["peak_memory_bytes"] \
        == res.resources["peak_memory_bytes"]
    for r in recs:
        if r["kind"] == "sample":
            assert r["resources"] == res.resources


def test_stream_stats_carry_resources():
    stats = serve_multitenant(
        [StreamSpec("probe0", tiny_config(), fps=1e9, n_frames=8)],
        policy=BatchPolicy(max_batch=2), in_flight=2)
    res = stats["resources"]
    assert res["peak_memory_bytes"] is not None
    assert res["memory_source"] == "live_arrays"
    assert res["energy_joules"] is None          # graceful off-GPU
    (group,) = stats["groups"].values()
    json.dumps(group["plan"])                    # stamp stays serializable
    assert group["plan"]["devices"] == 1


def test_meter_survives_broken_energy_backend():
    class Exploding:
        def available(self):
            return True

        def start(self):
            raise RuntimeError("driver gone")

        def stop(self):
            raise RuntimeError("driver gone")

    meter = ResourceMeter(energy_meter=Exploding())
    meter.start()                                # exception-free contract
    st = meter.stop()
    assert isinstance(st, ResourceStats)
    assert st.energy_joules is None
