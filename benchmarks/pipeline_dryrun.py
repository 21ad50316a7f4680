"""Beyond-paper: the ultrasound pipeline at pod scale.

The paper runs one chip. Here the full-CNN B-mode pipeline (exact
published geometry, 5.472 MB per acquisition) is sharded over the
production mesh — acquisitions (a leading batch of independent RF frames
sets) over the data axis, image pixels of the interpolation operator over
the model axis — and lowered/compiled like any LM dry-run cell, with the
same roofline terms. This is the "large-array / high-frame-rate" regime
the paper's §VII motivates (their compressor module targets it).

  PYTHONPATH=src python -m benchmarks.pipeline_dryrun
"""

import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import json

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import Variant, paper_config
from repro.core.pipeline import init_pipeline, pipeline_fn
from repro.launch import hlo_cost
from repro.launch import hlo_analysis as hlo
from repro.launch.dryrun import append_result
from repro.launch.mesh import make_production_mesh

OUT = os.path.join(os.path.dirname(__file__), "results",
                   "dryrun_optimized.json")

N_ACQ = 256  # simultaneous acquisitions (a probe-array farm / batch job)


def main():
    cfg = paper_config(variant=Variant.CNN)
    mesh = make_production_mesh()
    consts_np = init_pipeline(cfg)
    consts_abs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), consts_np)
    rf_abs = jax.ShapeDtypeStruct((N_ACQ,) + cfg.rf_shape, jnp.int16)

    fn = pipeline_fn(cfg)
    batched = jax.vmap(fn, in_axes=(None, 0))

    const_shardings = jax.tree.map(
        lambda a: NamedSharding(mesh, P()), consts_abs)
    # the big interpolation operator shards its pixel dim over model
    for half in ("interp_re", "interp_im"):
        const_shardings[half] = NamedSharding(mesh, P(None, None, "model"))
    rf_sharding = NamedSharding(mesh, P("data"))

    with jax.set_mesh(mesh):
        lowered = jax.jit(
            batched,
            in_shardings=(const_shardings, rf_sharding)).lower(
                consts_abs, rf_abs)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = hlo_cost.analyze(compiled.as_text())
    n_chips = mesh.devices.size
    terms = hlo.roofline_terms(cost.flops, cost.bytes_min,
                               int(cost.coll_bytes), n_chips)
    total = max(terms.values())
    in_bytes = N_ACQ * cfg.input_bytes
    print(json.dumps({
        "cell": "ultrasound-bmode-cnn x 256 acquisitions",
        "mesh": "single(16x16)",
        "roofline": terms,
        "dominant": hlo.dominant_term(terms),
        "per_device_temp_gb": mem.temp_size_in_bytes / 1e9,
        "predicted_throughput_GBps": in_bytes / total / 1e9,
        "predicted_fps_per_pass": 1.0 / total,
        "images_per_second": N_ACQ * cfg.n_f / total,
    }, indent=2))

    record = {
        "arch": "ultrasound-bmode-cnn-batch256", "shape": "paper_5.472MB",
        "mesh": "single", "n_chips": int(n_chips), "status": "ok",
        "roofline": terms, "dominant": hlo.dominant_term(terms),
        "flops_per_device": cost.flops, "bytes_per_device": cost.bytes_min,
        "bytes_per_device_max": cost.bytes, "collective_total":
        int(cost.coll_bytes),
        "collective_bytes": {k: int(v) for k, v in cost.coll.items()},
        "memory": {"argument_bytes": int(mem.argument_size_in_bytes),
                   "output_bytes": int(mem.output_size_in_bytes),
                   "temp_bytes": int(mem.temp_size_in_bytes),
                   "generated_code_bytes": 0},
        "model_flops_global": 0, "model_flops_per_device": 0,
        "useful_ratio": 0, "params_total": 0, "params_active": 0,
        "unknown_trip_loops": cost.unknown_loops,
        "compile_s": 0,
    }
    append_result(record, OUT)


if __name__ == "__main__":
    main()
