"""The harness end to end on the CPU, with a tiny test-only configuration.

What runs here is ``benchmark/run.py``'s own ``main``, told to accept the
CPU backend: the cell is resolved from data files, the driver is loaded by
name, the reference check runs, the window is measured and, with
``--trace 1``, traced and reduced.  The line it prints says ``"platform":
"cpu"``, and nothing it measures is a device number or is written anywhere.
"""

import json
import os

import pytest

from benchmark import run
from benchmark.lib import harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# each real cell's stand-in, by its traffic mix
TINY = {"caffenet_train_resident": "tiny_resident",
        "googlenet_train_resident": "tiny_resident",
        "caffenet_train_fed": "tiny_fed",
        "caffenet_rounds_x4": "tiny_rounds"}


@pytest.fixture(scope="module")
def spec_path(tmp_path_factory):
    """``BENCHMARK.json`` with its metrics kept and its configurations and
    cells replaced by the tiny ones."""
    spec = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    spec["configs"] = [{"name": "lenet_tiny", "file": os.path.relpath(
        os.path.join(DATA, "lenet_tiny.json"), harness.REPO)}]
    spec["workloads"] = [
        {"name": name, "config": "lenet_tiny", "traffic": name,
         "chips": 4 if name == "tiny_rounds" else 1}
        for name in sorted(set(TINY.values()))]
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if "workloads" in m:
                m["workloads"] = sorted({TINY[w] for w in m["workloads"]})
    path = tmp_path_factory.mktemp("spec") / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return str(path)


def run_cell(capsys, monkeypatch, tmp_path, spec_path, workload, trace):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    rc = run.main(["--workload", workload, "--seed", "3", "--seconds", "1.5",
                   "--trace", str(trace)],
                  spec_path=spec_path, platform="cpu",
                  traffic_dir=os.path.join(DATA, "traffic"),
                  cache_dir=str(tmp_path / "cache"))
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), [json.loads(l) for l in lines[:-1]]


@pytest.mark.parametrize("workload", sorted(set(TINY.values())))
def test_end_to_end_line(capsys, monkeypatch, tmp_path, spec_path, workload):
    out, earlier = run_cell(capsys, monkeypatch, tmp_path, spec_path,
                            workload, 0)
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    rate = "train_fed_img_s" if workload == "tiny_fed" else "train_img_s"
    assert set(out["metrics"]) == {rate, "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"        # never a device number
    cell = next(e["cell"] for e in earlier if "cell" in e)
    assert {"fuse_plan", "tune_plan", "jax", "libtpu"} <= set(cell)
    window = next(e["window"] for e in earlier if "window" in e)
    assert window["compile_events_in_window"] == 0
    assert len(window["unit_s"]) > 0


def test_fed_cell_reads_the_shards_it_wrote(capsys, monkeypatch, tmp_path,
                                            spec_path):
    out, earlier = run_cell(capsys, monkeypatch, tmp_path, spec_path,
                            "tiny_fed", 0)
    counters = next(e["counters"] for e in earlier if "counters" in e)
    assert counters["feed_records"]["read_s"] > 0
    assert counters["feed_device"]["batches"] >= out["attempted"]
    (dataset,) = os.listdir(tmp_path / "cache")
    assert sorted(os.listdir(tmp_path / "cache" / dataset)) == [
        "data", "order-s3"]


def test_off_the_asked_platform_nothing_runs(capsys, spec_path):
    rc = run.main(["--workload", "tiny_resident", "--seed", "0",
                   "--seconds", "1", "--trace", "0"], spec_path=spec_path,
                  traffic_dir=os.path.join(DATA, "traffic"))
    assert rc != 0
    assert capsys.readouterr().out == ""
