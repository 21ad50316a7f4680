"""Multi-device sharded executor: allclose vs. the single-device oracle.

The real multi-device checks run in a subprocess with 2 forced CPU host
devices (XLA locks the host device count at first jax init — the main
pytest process must keep seeing 1 device, same pattern as
test_sharding_spmd.py). The main process covers the degenerate 1-device
mesh, plan stamping, and API error paths.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import BatchedExecutor, ShardedExecutor, tiny_config
from repro.data import synth_rf


def _rf_batch(cfg, n, seed0=0):
    return jnp.stack([jnp.asarray(synth_rf(cfg, seed=seed0 + i))
                      for i in range(n)])


# ---------------------------------------------------------------------------
# Main process: 1-device mesh (degenerate but fully functional)
# ---------------------------------------------------------------------------


def test_single_device_mesh_matches_batched():
    cfg = tiny_config()
    rf = _rf_batch(cfg, 3)
    sharded = ShardedExecutor(cfg)
    batched = BatchedExecutor(cfg)
    np.testing.assert_allclose(np.asarray(sharded(rf)),
                               np.asarray(batched(rf)),
                               rtol=1e-5, atol=1e-6)


def test_plan_carries_device_topology():
    cfg = tiny_config()
    eng = ShardedExecutor(cfg)
    n = len(jax.local_devices())
    assert eng.plan.devices == n
    assert eng.plan.mesh_shape == (("data", n),)
    d = eng.plan.json_dict()
    assert d["devices"] == n and d["mesh_shape"] == [["data", n]]
    # the batched executor stays a single-device plan
    assert BatchedExecutor(cfg).plan.devices == 1
    assert BatchedExecutor(cfg).plan.json_dict()["mesh_shape"] is None


def test_with_devices_rejects_inconsistent_mesh():
    from repro.core import plan_pipeline
    plan = plan_pipeline(tiny_config())
    with pytest.raises(AssertionError):
        plan.with_devices(2, (("data", 3),))


def test_empty_device_list_rejected():
    with pytest.raises(ValueError):
        ShardedExecutor(tiny_config(), devices=())


def test_name_includes_device_count():
    eng = ShardedExecutor(tiny_config())
    assert eng.name.endswith(f":d{eng.n_devices}")


# ---------------------------------------------------------------------------
# Subprocess: forced 2-host-device CPU mesh
# ---------------------------------------------------------------------------

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import BatchedExecutor, ShardedExecutor, tiny_config
from repro.data import synth_rf
from repro.launch.scheduler import BatchPolicy, StreamSpec, \
    serve_multitenant

out = {"device_count": jax.device_count()}

cfg = tiny_config()
oracle = BatchedExecutor(cfg)
eng = ShardedExecutor(cfg)
out["plan_devices"] = eng.plan.devices
out["mesh_shape"] = eng.plan.json_dict()["mesh_shape"]

# allclose vs. the single-device oracle: even batch, remainder, B < devices
errs = {}
for B in (4, 3, 1):
    rf = jnp.stack([jnp.asarray(synth_rf(cfg, seed=i)) for i in range(B)])
    got = np.asarray(eng(rf))
    want = np.asarray(oracle(rf))
    errs[str(B)] = [list(got.shape) == list(want.shape),
                    float(np.abs(got - want).max())]
out["errs"] = errs

# dispatch_staged refuses a pad_to that is not a device multiple (one
# SPMD shape per mesh: serving must stay device-aligned)
try:
    eng.dispatch_staged(
        eng.place(np.stack([synth_rf(cfg, seed=0)] * 3)), 3)
    out["dispatch_remainder_raised"] = False
except ValueError:
    out["dispatch_remainder_raised"] = True

# one output shard per device
disp = eng.dispatch_staged(
    eng.place(np.stack([synth_rf(cfg, seed=i) for i in range(4)])), 4)
out["shard_devices"] = sorted(str(s.device) for s in disp.addressable_shards)
out["shard_batches"] = [s.data.shape[0] for s in disp.addressable_shards]

# exec_map="map": shard_map keeps the scan per-device — allclose to the
# oracle and no all-gather of the batch in the compiled program
cfg_m = cfg.with_(exec_map="map")
eng_m = ShardedExecutor(cfg_m)
oracle_m = BatchedExecutor(cfg_m)
rf4 = jnp.stack([jnp.asarray(synth_rf(cfg, seed=i)) for i in range(4)])
out["map_err"] = float(np.abs(np.asarray(eng_m(rf4))
                              - np.asarray(oracle_m(rf4))).max())
hlo = eng_m.jitted.lower(eng_m.consts, rf4).compile().as_text()
out["map_has_allgather"] = "all-gather" in hlo.lower()

# one probe served over both devices: every batch split across the mesh
stats = serve_multitenant(
    [StreamSpec("probe0", cfg, fps=1e9, n_frames=24)],
    policy=BatchPolicy(max_batch=4), devices=jax.local_devices(),
    collect_outputs=True)
out["stream"] = {
    "plan_devices": [g["plan"]["devices"]
                     for g in stats["groups"].values()],
    "mesh_shape": [g["plan"]["mesh_shape"]
                   for g in stats["groups"].values()],
    "max_batch": stats["policy"]["max_batch"],
    "acquisitions": stats["acquisitions"],
    "served": len(stats["outputs"]["probe0"]),
    "batches": stats["occupancy"]["batches"],
    "max_occupancy": stats["occupancy"]["max_occupancy"],
    "resources": stats["resources"],
}

print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    # Keep the subprocess's on-disk caches out of the checkout, like
    # conftest does in-process.
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("subproc-xla-cache"))
    env["REPRO_CONSTS_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("subproc-consts-cache"))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_two_device_mesh_active(results):
    assert results["device_count"] == 2
    assert results["plan_devices"] == 2
    assert results["mesh_shape"] == [["data", 2]]


def test_allclose_vs_single_device_oracle(results):
    for b, (shape_ok, err) in results["errs"].items():
        assert shape_ok, f"batch {b}: shape mismatch"
        assert err < 1e-5, f"batch {b}: max abs err {err}"


def test_uneven_remainder_handling(results):
    # B=3 and B=1 exercised __call__'s padding above; dispatch_staged
    # must refuse a pad_to that does not split across the mesh
    assert results["dispatch_remainder_raised"] is True


def test_one_shard_per_device(results):
    assert len(results["shard_devices"]) == 2
    assert len(set(results["shard_devices"])) == 2
    assert results["shard_batches"] == [2, 2]       # 4 acqs split 2+2


def test_exec_map_map_stays_data_parallel(results):
    """lax.map under sharding must scan per-device shards (shard_map),
    never all-gather the batch onto every device."""
    assert results["map_err"] < 1e-5
    assert results["map_has_allgather"] is False


def test_sharded_stream_stats(results):
    """The serving loop over a 2-device mesh: the plan stamp names the
    mesh and every frame of the probe is served."""
    s = results["stream"]
    assert s["plan_devices"] == [2]
    assert s["mesh_shape"] == [[["data", 2]]]
    assert s["max_batch"] == 4                      # 2 per device x 2
    assert s["acquisitions"] == s["served"] == 24   # every frame drained
    assert s["batches"] >= 24 // 4
    assert s["max_occupancy"] <= 4
    assert s["resources"]["peak_memory_bytes"] is not None
    assert s["resources"]["energy_joules"] is None  # no NVML on CI/CPU
