"""CPU rehearsal of every cell: traffic, warm-up, window, comparison with
the reference and the result line, at a small size."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import cells as C, control, devices, run as R
from bench.tests.helpers import (ROOT, SMALL_DIR, in_four_devices,
                                 load_spec, measure_small, small_cell,
                                 small_path)

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks",
        "counters"]
CELLS = load_spec()["workloads"]


@pytest.mark.parametrize("workload", [c["name"] for c in CELLS
                                      if c["chips"] == 1])
def test_cell_runs_and_matches_the_reference(workload):
    out = measure_small(workload)
    assert list(out) == KEYS
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    e2e, _ = R.metric_sets(load_spec(), workload)
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


def test_compile_cache_settings_are_the_processs_own_again(monkeypatch):
    """The run keys the compile cache on op metadata, where the programs'
    scopes live, and gives the process its own settings back."""
    import jax

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_compilation_cache_include_metadata_in_key")
    before = {k: getattr(jax.config, k) for k in keys}
    seen = {}
    window = C.RnpCell.window

    def seeing(self, seconds):
        seen.update({k: getattr(jax.config, k) for k in keys})
        window(self, seconds)

    monkeypatch.setattr(C.RnpCell, "window", seeing)
    assert measure_small("gnm11.rnp")["correct"]
    assert seen["jax_compilation_cache_include_metadata_in_key"] is True
    assert seen["jax_persistent_cache_min_compile_time_secs"] == 0
    assert {k: getattr(jax.config, k) for k in keys} == before


#: the files a later change adds for a new graph family and cell kind
FAMILY = """
import numpy as np

from bench import graphs


def generate(n, config, seed):
    pairs = np.stack([np.arange(n - 1), np.arange(1, n)], axis=1)
    w = graphs.rng_of(seed).integers(config["weight_lo"],
                                     config["weight_hi"] + 1, size=n)
    return graphs.csr(n, pairs, w)
"""
KIND = """
from bench.cells import ReduceCell


class Cell(ReduceCell):
    def setup(self):
        super().setup()
        self.counters["graphs_set_up"] = len(self.graphs)
"""
READER = """
def read(run):
    return run.cell.graphs[0][2].shape[0]
"""


def file_hashes(top):
    return {str(p.relative_to(top)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in top.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path):
    """A graph family, a cell kind, a configuration, a traffic mix, its
    small size and a metric reader that a later change adds are found from
    BENCHMARK.json by name: a copy of the benchmark with only those files
    and entries added runs the new cell, untraced and traced, and its
    control, and no file that was there is edited."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = file_hashes(tmp_path / "bench")
    spec = load_spec()
    conf = json.loads((tmp_path / "bench/configs/gnm-d8.json").read_text())
    new = {"configs/path-d2.json": json.dumps(dict(conf, name="path-d2",
                                                   family="path")),
           "traffic/path64.json": json.dumps(dict(
               kind="reduce_counted", pes=1, n_per_pe=64, graph_seeds=[9])),
           "tests/small/path.reduce.json": json.dumps(dict(n_per_pe=32)),
           "families/path.py": FAMILY, "kinds/reduce_counted.py": KIND,
           "metrics/vertices.reduce.py": READER}
    for rel, text in new.items():
        os.makedirs(os.path.dirname(tmp_path / "bench" / rel), exist_ok=True)
        (tmp_path / "bench" / rel).write_text(text)
    spec["configs"].append(dict(name="path-d2", source="x",
                                file="bench/configs/path-d2.json",
                                reduced=[], why="x"))
    spec["workloads"].append(dict(name="path.reduce", config="path-d2",
                                  traffic="path64", chips=1, why="x"))
    next(m for m in spec["end_to_end"]
         if m["name"] == "reduce_s")["workloads"].append("path.reduce")
    spec["per_layer"].append(dict(name="vertices.reduce", unit="1",
                                  better="higher", source="host_clock",
                                  layer="host build", moves="reduce_s",
                                  workloads=["path.reduce"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    _, _, config, traffic = small_cell("path.reduce", root)
    assert config["family"] == "path" and traffic["n_per_pe"] == 32
    out = measure_small("path.reduce", root=root)
    assert out["correct"] and out["counters"]["graphs_set_up"] == 1
    assert set(out["metrics"]) == {"reduce_s", "setup_s"}
    assert all(c["value"] == 0 for c in out["checks"].values())
    out = measure_small("path.reduce", root=root, trace=True)
    assert out["correct"]
    assert out["metrics"]["vertices.reduce"] == dict(value=32, unit="1")
    ctl = control.control("path.reduce", 1, root, traffic=traffic)
    assert set(ctl["checks"]) == set(out["checks"])
    assert not ctl["correct"], ctl["checks"]
    after = file_hashes(tmp_path / "bench")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == set(new)


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


SMALL = [f[:-len(".json")] for f in os.listdir(os.path.join(ROOT, SMALL_DIR))]


@pytest.mark.parametrize("workload", sorted({c["name"] for c in CELLS}
                                            | set(SMALL)))
def test_small_sizes_name_real_cells(workload):
    """Every cell has a small size for the CPU, and every small size names
    a cell and overrides keys of its traffic file."""
    _, _, traffic = R.cell_spec(load_spec(), workload)
    assert os.path.isfile(small_path(workload))
    assert set(R.load_json(small_path(workload))) <= set(traffic)
