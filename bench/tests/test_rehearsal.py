"""CPU rehearsal of every cell: traffic, warm-up, window, comparison with
the reference and the result line, at a small size."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import devices, run as R
from bench.tests.helpers import (LATER_CELL, ROOT, SMALL, in_four_devices,
                                 measure_small)

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks",
        "counters"]


@pytest.mark.parametrize("workload", ["gnm18.reduce", "gnm11.rnp",
                                      "serve.mix16"])
def test_cell_runs_and_matches_the_reference(workload):
    out = measure_small(workload)
    assert list(out) == KEYS
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    spec = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    e2e, _ = R.metric_sets(spec, workload)
    assert sorted(out["metrics"]) == sorted(m["name"] for m in e2e)
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert all(c["value"] == 0 for c in out["checks"].values())
    assert out["counters"]["window_compiles"] == 0


def test_traced_run_reports_per_layer_metrics():
    out = measure_small("gnm11.rnp", trace=True)
    assert out["correct"]
    assert {"host_build_s", "peel_ms.rnp"} <= set(out["metrics"])
    c = out["counters"]
    assert out["metrics"]["peel_ms.rnp"]["value"] == pytest.approx(
        1e3 * sum(c["call_s"]) / c["peels"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(out["device"])


def test_four_pe_cell_on_four_cpu_devices():
    out = in_four_devices(
        "import json\nfrom bench.tests.helpers import measure_small\n"
        "out = measure_small('gnm14x4.reduce')\n"
        "print(json.dumps(dict(correct=out['correct'], "
        "compiles=out['counters']['window_compiles'])))")
    assert out == dict(correct=True, compiles=0)


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, traffic mix or metric reader that a later change
    adds is found from BENCHMARK.json by its name; no file is edited."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    conf = json.load(open(tmp_path / "bench/configs/gnm-d8.json"))
    json.dump(dict(conf, name="gnm-d8b"),
              open(tmp_path / "bench/configs/gnm-d8b.json", "w"))
    json.dump(dict(kind="reduce", pes=1, n_per_pe=64, graph_seeds=[9]),
              open(tmp_path / "bench/traffic/tiny.json", "w"))
    (tmp_path / "bench/metrics/calls_seen.reduce.py").write_text(
        "def read(run):\n    return run.counters['calls']\n")
    spec["configs"].append(dict(name="gnm-d8b", source="x",
                                file="bench/configs/gnm-d8b.json",
                                reduced=[], why="x"))
    spec["workloads"].append(dict(name="b.tiny", config="gnm-d8b",
                                  traffic="tiny", chips=1, why="x"))
    spec["end_to_end"][0]["workloads"].append("b.tiny")
    spec["per_layer"].append(dict(name="calls_seen.reduce", unit="1",
                                  better="higher", source="host_clock",
                                  layer="round driver", moves="reduce_s"))
    cell, config, traffic = R.cell_spec(spec, "b.tiny", str(tmp_path))
    assert config["name"] == "gnm-d8b" and traffic["n_per_pe"] == 64
    e2e, layer = R.metric_sets(spec, "b.tiny")
    assert "reduce_s" in {m["name"] for m in e2e}
    assert "calls_seen.reduce" in {m["name"] for m in layer}

    class Run:
        counters = dict(calls=7)

    assert R.reader("calls_seen.reduce", str(tmp_path))(Run) == 7


def test_measurement_refuses_a_cpu(capsys):
    with pytest.raises(devices.NoChip):
        devices.gate(1)
    assert R.main(["--workload", "gnm18.reduce", "--seed", "1",
                   "--seconds", "1"]) == 1
    assert capsys.readouterr().out == ""


def test_exits_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gnm18.reduce",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout == ""


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_small_sizes_name_real_cells(workload):
    spec = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {c["name"] for c in spec["workloads"]}
    assert workload in cells or workload == LATER_CELL["name"]
    assert set(SMALL) >= cells
