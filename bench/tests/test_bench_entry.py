"""The measuring entry, BENCHMARK.json's shape, and finding a cell's
files by name."""

import json
import os
import re
import shutil

import pytest

from bench import rf, run, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_entry_refuses_a_host_without_a_tpu(capsys):
    rc = run.main(["--workload", "paper-bmode.backlog", "--seed",
                   str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 2
    assert "{" not in out
    assert "no TPU" in err


def test_benchmark_file_keeps_its_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]]
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
        assert c["file"].startswith("bench/configs/")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(spec.BENCH_DIR, "traffic",
                                           w["traffic"] + ".json"))
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.isfile(os.path.join(spec.METRICS_DIR,
                                           m["name"] + ".py"))
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    assert all(m["moves"] in e2e for m in bench["per_layer"])
    assert all(len(x) <= 200 and "\n" not in x for x in layers)
    assert len({m["name"] for m in metrics}) == len(metrics)


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        cell = spec.cell(bench, w["name"])
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        served = set(cell.config["probes"])
        assert served <= set(cell.config["limits"])


def test_cells_take_their_metrics_by_workload(bench):
    backlog = spec.cell(bench, "paper-bmode.backlog")
    fleet = spec.cell(bench, "paper-mixed.fleet")
    assert {m["name"] for m in backlog.end_to_end} == {"throughput_MBps",
                                                      "setup_s"}
    assert {m["name"] for m in fleet.end_to_end} == {
        "latency_p50_ms", "latency_p95_ms", "setup_s"}
    assert {m["name"] for m in fleet.per_layer} == {
        "queue_delay_p95_ms", "batch_occupancy_mean"}
    assert "pipeline_mfu_pct" in {m["name"] for m in backlog.per_layer}


def test_a_new_metric_file_is_found_by_name(small_cell, cpu, tmp_path,
                                            monkeypatch):
    for name in os.listdir(spec.METRICS_DIR):
        if name.endswith(".py"):
            shutil.copy(os.path.join(spec.METRICS_DIR, name), tmp_path)
    (tmp_path / "served_count.x-1.py").write_text(
        "def read(ctx):\n    return ctx.served\n")
    (tmp_path / "served_bytes.py").write_text(
        "def read(ctx):\n    return ctx.served_bytes\n")
    monkeypatch.setattr(spec, "METRICS_DIR", str(tmp_path))
    cell = small_cell()
    cell.end_to_end = cell.end_to_end + [
        {"name": name, "unit": unit, "better": "higher", "bound": 0.01,
         "source": "host_clock"}
        for name, unit in (("served_count.x-1", "acq"), ("served_bytes", "B"))]
    res = run.run_cell(cell, 9, 0.1, False, devices=cpu, log=lambda s: None)
    assert res["metrics"]["served_count.x-1"] == {"value": 16.0,
                                                  "unit": "acq"}
    # The served bytes are the benchmark's own RF's, not the program's
    # count: 16 acquisitions of int16 (n_l, n_c, n_f).
    g = cell.config["pipeline"]
    rf_bytes = rf.make_pool(g, cell.config["rf"], [1])[0].nbytes
    assert rf_bytes == g["n_l"] * g["n_c"] * g["n_f"] * 2
    assert res["metrics"]["served_bytes"]["value"] == 16.0 * rf_bytes
    assert list(res)[-1] == "checks"
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["metrics"]["setup_s"]["value"] > 0
    json.dumps(res)
