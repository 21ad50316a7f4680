"""Stage scopes, device executions and the serving loop's spans, read
from the profiler trace of a window.

`bench/trace.py` reduces a traced window to device busy time. This
module reads three more things from the same ``.xplane.pb``:

  ``scopes``      {plane: [[scope, start_ns, end_ns], ...]}: every
                  operation of the device's "XLA Ops" line (the line
                  bench/trace.py reads), named by the stage scope of its
                  instruction: ``demod``, ``beamform``, the head or a
                  fused group, "" outside every stage. The program runs
                  each stage under ``jax.named_scope``
                  (`repro.core.stages.graph_fn`), which lands in the
                  compiled instruction's ``op_name`` metadata. The TPU
                  v5e's op events carry no ``op_name``, so the scope is
                  looked up in the compiled programs' HLO text, by
                  instruction, in the program whose instructions the
                  execution ran;
  ``executions``  {plane: [[module, start_ns, end_ns], ...]}: the
                  device's "XLA Modules" line, one event per execution
                  of a program;
  ``spans``       [[name, idx, group, start_ns, end_ns], ...]: the
                  serving loop's ``serve.*`` spans
                  (`repro.launch.scheduler.serve_multitenant`), with the
                  batch's launch index and group key (None for
                  ``serve.poll`` and ``serve.idle``).

`summarize` reduces them over the steady slice of `bench.trace.summarize`
(it needs that module's ``markers``): device seconds per stage scope,
and, for each batch launched inside the slice, its device queue (its
execution's start minus the end of its ``serve.launch``) and its detect
lag (its ``serve.detect`` minus its execution's end). Batches are
matched to executions in launch order: one chip has one device queue,
which every group's program shares.

    python bench/stage_trace.py --workload <cell> --seed <n> --seconds <s>

makes one traced run of a cell, as ``bench/run.py --trace 1`` does, and
prints after its result line one JSON line of these numbers.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Tuple

SPAN = "serve."
OP_LINES = ("XLA Ops",)
EXECUTION_LINES = ("XLA Modules",)
STAGES = ("demod", "beamform", "bmode", "doppler", "power_doppler")
HEADS = ("bmode", "doppler", "power_doppler")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+ = .*? [a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def scope_of(op_name: str) -> str:
    """The stage scope in an ``op_name`` path: the first component that,
    unwrapped from its transformations (``vmap(demod)``), is a stage or
    a fused group (``demod+beamform+bmode``); "" when none is."""
    for part in op_name.split(";")[0].split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part in STAGES or "+" in part:
            return part
    return ""


def instruction(text: str) -> Optional[str]:
    """``%name = type opcode`` of an HLO instruction: the same in the
    compiled program's text and in the name of the trace's op event."""
    m = _INSTRUCTION.match(text)
    return m.group(1) if m else None


def program_scopes(hlo_texts: Iterable[str]) -> List[Dict[str, str]]:
    """{instruction: stage scope} of each compiled program's HLO text."""
    tables = []
    for text in hlo_texts:
        table = {}
        for line in text.splitlines():
            ins = instruction(line)
            if ins is not None:
                m = _OP_NAME.search(line)
                table[ins] = scope_of(m.group(1)) if m else ""
        tables.append(table)
    return tables


def _scope_ops(ops, execs, tables) -> List[List]:
    """[[scope, start, end]] of each op: the op's instruction looked up
    in the program that covers most of its execution's instructions."""
    starts = [s for _, s, _ in execs]
    by_exec = collections.defaultdict(list)
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        inside = i >= 0 and s <= execs[i][2]
        by_exec[i if inside else None].append((instruction(name), s, e))
    chosen: Dict[str, Dict[str, str]] = {}
    out = []
    for i, group in by_exec.items():
        table: Dict[str, str] = {}
        if i is not None and tables:
            module = execs[i][0]
            if module not in chosen:
                chosen[module] = max(
                    tables, key=lambda t: sum(ins in t for ins, _, _ in group))
            table = chosen[module]
        out += [[table.get(ins, ""), s, e] for ins, s, e in group]
    return sorted(out, key=lambda op: op[1])


def events(log_dir: str, n_devices: int, hlo_texts: Iterable[str]) -> Dict:
    """``scopes``, ``executions`` and ``spans`` of the trace under
    ``log_dir``, for the same device planes `bench.trace.events` keeps."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    tables = program_scopes(hlo_texts)
    scopes: Dict[str, List] = {}
    executions: Dict[str, List] = {}
    spans: List[List] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, execs = [], []
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops += [(e.name, e.start_ns, e.end_ns)
                            for e in line.events]
                elif line.name in EXECUTION_LINES:
                    execs += [[e.name, e.start_ns, e.end_ns]
                              for e in line.events]
            if ops:
                execs.sort(key=lambda x: x[1])
                scopes[plane.name] = _scope_ops(ops, execs, tables)
                executions[plane.name] = execs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN):
                        args = dict(e.stats)
                        spans.append([e.name, args.get("idx"),
                                      args.get("group"), e.start_ns,
                                      e.end_ns])
    used = sorted(scopes)[:n_devices]
    return {"scopes": {p: scopes[p] for p in used},
            "executions": {p: executions[p] for p in used},
            "spans": sorted(spans, key=lambda s: s[3])}


def match_batches(executions: Dict[str, List], spans: List[List]
                  ) -> Tuple[Optional[List[Dict]], Optional[str]]:
    """Each launched batch with its execution, in launch order:
    ([{idx, group, launch_start_ns, device_queue_s, detect_lag_s}], None),
    or (None, why they do not match one to one)."""
    launches = [s for s in spans if s[0] == SPAN + "launch"]
    detects = collections.defaultdict(list)
    for s in spans:
        if s[0] == SPAN + "detect":
            detects[s[1]].append(s)
    planes = list(executions.values())
    if not planes or not launches:
        return None, "the trace holds no device executions or launches"
    counts = sorted({len(p) for p in planes})
    if len(counts) > 1:
        return None, f"the devices ran {counts} executions"
    if sorted(s[1] for s in launches) != list(range(len(launches))):
        return None, "the launch indices are not 0..n-1, each once"
    if counts[0] != len(launches):
        return None, (f"{counts[0]} device executions for "
                      f"{len(launches)} launched batches")
    batches = []
    for name, idx, group, start, end in sorted(launches,
                                               key=lambda s: s[1]):
        if len(detects[idx]) != 1:
            return None, (f"batch {idx} has {len(detects[idx])} "
                          "serve.detect spans")
        x0 = min(p[idx][1] for p in planes)
        x1 = max(p[idx][2] for p in planes)
        batches.append({"idx": idx, "group": group,
                        "launch_start_ns": start,
                        "device_queue_s": (x0 - end) / 1e9,
                        "detect_lag_s": (detects[idx][0][3] - x1) / 1e9})
    return batches, None


def summarize(ev: Dict, trim: float = 0.1) -> Dict:
    """Over the steady slice of `bench.trace.summarize`: ``window_s``,
    ``stage_s`` {scope: device seconds}, ``unscoped_s``, and ``batches``
    (those launched in the slice; None, with ``unmatched`` saying why,
    when batches and executions do not match one to one)."""
    from bench.trace import LANDED, OPEN, _clip, _union
    w0 = min(ev["markers"][OPEN])
    w1 = max(ev["markers"][LANDED])
    lo, hi = w0 + trim * (w1 - w0), w1 - trim * (w1 - w0)
    per_scope: Dict[str, float] = collections.defaultdict(float)
    for ops in ev["scopes"].values():
        by_scope = collections.defaultdict(list)
        for scope, a, b in ops:
            by_scope[scope].append((a, b))
        for scope, intervals in by_scope.items():
            per_scope[scope] += sum(
                b - a for a, b in _union(_clip(intervals, lo, hi)))
    n = max(1, len(ev["scopes"]))
    batches, unmatched = match_batches(ev["executions"], ev["spans"])
    if batches is not None:
        batches = [b for b in batches if lo <= b["launch_start_ns"] <= hi]
    return {"window_s": (hi - lo) / 1e9,
            "stage_s": {k: v / n / 1e9 for k, v in sorted(per_scope.items())
                        if k},
            "unscoped_s": per_scope.get("", 0.0) / n / 1e9,
            "batches": batches, "unmatched": unmatched}


def layer_metrics(summary: Dict, busy_s: float,
                  device_ms_per_acq: Optional[float]) -> Dict[str, float]:
    """The per-layer numbers of one summary: each stage's share of the
    device's busy time scaled to ``device_ms_per_acq`` (so they add up
    to it with the unscoped time), and the batches' 95th percentiles."""
    import numpy as np
    out: Dict[str, float] = {}
    if device_ms_per_acq is not None and busy_s > 0:
        def per_acq(seconds):
            return seconds / busy_s * device_ms_per_acq
        stage = summary["stage_s"]
        out["demod_ms_per_acq"] = per_acq(stage.get("demod", 0.0))
        out["beamform_ms_per_acq"] = per_acq(stage.get("beamform", 0.0))
        out["head_ms_per_acq"] = per_acq(sum(stage.get(h, 0.0)
                                             for h in HEADS))
        out["unscoped_ms_per_acq"] = per_acq(summary["unscoped_s"])
    if summary["batches"]:
        for key in ("device_queue", "detect_lag"):
            out[key + "_p95_ms"] = float(np.percentile(
                [b[key + "_s"] for b in summary["batches"]], 95)) * 1e3
    return out


def traced_run(cell, seed: int, seconds: float, *, devices,
               log=print) -> Tuple[Dict, Dict]:
    """One ``bench/run.py --trace 1`` run of ``cell``; returns its result
    and its events: bench/trace.py's and this module's, read from the
    same trace (run_cell deletes it once bench/trace.py has read it),
    with the HLO of the programs the warm pool compiled."""
    import repro.core.aot as aot
    from bench import run
    from bench import trace as tr

    pools, merged = [], {}
    warm_pool, trace_events = aot.warm_pool, tr.events

    def keep_pool(*a, **kw):
        pools.append(warm_pool(*a, **kw))
        return pools[-1]

    def both_events(log_dir, n_devices):
        ev = trace_events(log_dir, n_devices)
        hlo = [p.get(k).program.executable.as_text()
               for p in pools for k in p.keys()]
        merged.update(ev, **events(log_dir, n_devices, hlo))
        return ev

    aot.warm_pool, tr.events = keep_pool, both_events
    try:
        result = run.run_cell(cell, seed, seconds, True, devices=devices,
                              log=log)
    finally:
        aot.warm_pool, tr.events = warm_pool, trace_events
    return result, merged


def main(argv=None) -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--events-out", default=None,
                    help="write bench/trace.py's events and this "
                         "module's (JSON) here")
    args = ap.parse_args(argv)

    import jax
    from bench import spec
    cell = spec.cell(spec.load_benchmark(), args.workload)
    devices = jax.devices()[:cell.chips]
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"stage_trace: {args.workload} needs {cell.chips} TPU chips",
              file=sys.stderr)
        return 2
    result, ev = traced_run(cell, args.seed, args.seconds, devices=devices,
                            log=lambda s: print(s, flush=True))
    print(json.dumps(result), flush=True)
    if args.events_out:
        with open(args.events_out, "w") as f:
            json.dump(ev, f)
    s = summarize(ev)
    device_ms = result["metrics"].get("device_ms_per_acq", {}).get("value")
    print(json.dumps({
        "stage_trace": layer_metrics(s, result["device"]["busy_s"],
                                     device_ms),
        "stage_s": s["stage_s"], "unscoped_s": s["unscoped_s"],
        "busy_s": result["device"]["busy_s"], "slice_s": s["window_s"],
        "device_ms_per_acq": device_ms,
        "batches_in_slice": (None if s["batches"] is None
                             else len(s["batches"])),
        "unmatched": s["unmatched"]}), flush=True)
    return 0


if __name__ == "__main__":
    _bench = os.path.dirname(os.path.abspath(__file__))
    if sys.path and os.path.abspath(sys.path[0] or ".") == _bench:
        sys.path.pop(0)      # bench/trace.py must not shadow a module
    sys.path[:0] = [os.path.join(os.path.dirname(_bench), "src"),
                    os.path.dirname(_bench)]
    sys.exit(main())
