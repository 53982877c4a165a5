"""`serve.sparse_fetch_overhead` (PR 35): the K slabs the sparse walk's
copies bring in over the least it could, from two counters the model's step
returns. The real manifest grew by that one entry; the tiny MiniCPM-SALA
cell (`minicpm_sala/`, files as PR 34 left them) reads it through the real
runner on the CPU once its manifest is handed the entry."""
import dataclasses
import os
import time

import bench_rehearse as br
import test_benchmark_manifest as tm
from benchmark import harness

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "minicpm_sala")
CELL = "tiny-minicpm-longdoc"
REAL = "minicpm-sala-serve-longdoc"
NAME = "serve.sparse_fetch_overhead"


def test_the_manifest_grew_by_one_metric_of_the_kernels():
    """What `test_benchmark_minicpm_sala.py::test_the_manifest_only_grew`
    holds, with the list's tail as it stands: the new entry last, for the
    one cell whose program holds the kernel; nothing else moved."""
    tm.test_layer_metrics_agree_with_their_files(br.REPO)
    m = harness.load_manifest()
    assert [c["name"] for c in m["configs"]][-1] == "minicpm-sala-serve-d8"
    assert [w["name"] for w in m["workloads"]][-1] == REAL
    assert [x["name"] for x in m["per_layer"]][-4:] == [
        "serve.sparse_walk_overhead", "serve.sparse_selected_page_share",
        "serve.sparse_attention_roofline", NAME]
    assert m["per_layer"][-1] == {
        "name": NAME, "unit": "x", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p95_ms", "workloads": [REAL]}
    for x in m["per_layer"]:
        if x["name"].startswith("serve.") and "sparse" not in x["name"]:
            moe = "moe" in x["name"] or "expert" in x["name"]
            assert (REAL in x["workloads"]) != moe, x["name"]
            assert x["workloads"][-1] == REAL or moe
    assert next(x for x in m["end_to_end"] if x["name"] == "tpot_p95_ms")[
        "workloads"][-1] == REAL


def test_tiny_cell_reads_the_fetch_overhead(monkeypatch):
    """The tiny cell with the new entry in its manifest, through the real
    runner: a step of its 16 tokens is one wide query tile, so every page
    is fetched once (`tests/test_minicpm_sala.py` has chunks of three)."""
    import jax

    from benchmark import run as run_mod

    br.stand_in_tracer(monkeypatch)
    cell = harness.Cell.find(CELL, root=ROOT, bench_dir=ROOT)
    entry = dict(harness.load_manifest()["per_layer"][-1], workloads=[CELL])
    cell = dataclasses.replace(cell, manifest=dict(
        cell.manifest, per_layer=cell.manifest["per_layer"] + [entry]))
    devices = jax.devices()[:1]
    runner = harness.load_module("runners", cell.config["entry"])
    result = runner.run(cell, 2 ** 31 + 29, 1.0, True, devices,
                        time.perf_counter())
    line = run_mod.result_line(
        cell, result, devices, True,
        harness.load_json("peaks.json")["TPU v5 lite"])
    assert line["correct"] is True, line["compared"]
    m = line["metrics"]
    assert m[NAME]["value"] == 1
    assert m[NAME]["unit"] == "x"
    assert m["serve.sparse_walk_overhead"]["value"] >= 1


def test_a_program_without_the_counters_reads_nothing():
    """The parent's step returns four counts: the reader finds no such
    counter and leaves the metric out, as the driver's parent runs need."""
    from benchmark.readers import program_counter_ratio

    spec = harness.load_json("layer_metrics", NAME + ".json")
    assert spec["params"] == {"num": ["serving/sparse_slabs_fetched"],
                              "den": ["serving/sparse_slabs_least"]}
    gone = dict(spec["params"], num=["serving/no_such_counter"])
    assert program_counter_ratio.read(gone, None) is None
