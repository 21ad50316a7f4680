"""The control of the comparison: a lower precision in the program's place
must come out as not correct.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 3

Each seed is one run of the cell (bench/run.py's `run_cell`, the same
traffic and RF) in which the plain reference (bench/reference.py) serves
every batch in the program's place, its contractions in three bf16
passes (the TPU's ``Precision.HIGH``), one step below the float32 the
configuration states. It prints every number of every seed beside its
limit, and a last line of JSON: {"workload", "runs": [{"seed",
"correct", "checks"}]}. The benchmark's own runs never run it. Like
run.py it measures the chip only; the tests drive `control_run` on the
CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if sys.path and os.path.abspath(sys.path[0] or ".") == BENCH_DIR:
    sys.path.pop(0)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import reference, spec  # noqa: E402
from bench.run import run_cell  # noqa: E402


def reference_in_place(pipe):
    """A `run_cell` hook: every warmed executor serves the reference's
    images (three bf16 passes) instead of its own."""
    ref = reference.Reference(pipe, "bf16_3x")

    def hook(engine):
        modality = engine.cfg.modality.value
        ref(modality, np.zeros(engine.cfg.rf_shape, engine.cfg.rf_dtype))

        def dispatch_staged(dev_batch, pad_to):
            rows = np.asarray(dev_batch)
            return np.stack([ref(modality, r) for r in rows])
        engine.dispatch_staged = dispatch_staged
    return hook


def control_run(cell, seed: int, seconds: float, devices, log=print):
    """One run of ``cell`` with the reference in the program's place;
    returns run_cell's result."""
    return run_cell(cell, seed, seconds, False, devices=devices,
                    engine_hook=reference_in_place(cell.config["pipeline"]),
                    t_start=time.perf_counter(), log=log)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = spec.cell(spec.load_benchmark(), args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    runs = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = control_run(cell, seed, args.seconds, devices[:cell.chips])
        for name, c in res["checks"].items():
            print(f"control seed={seed} {name} value={c['value']!r} "
                  f"limit={c['limit']!r}", flush=True)
        runs.append({"seed": seed, "correct": res["correct"],
                     "checks": res["checks"]})
    print(json.dumps({"workload": args.workload, "runs": runs}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
