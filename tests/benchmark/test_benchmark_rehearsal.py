"""Each runner end to end on the CPU at a tiny configuration and a short
window, entered below the device check; and run.py itself refusing a CPU.
Kernels run as their jnp references here (the tiny heads do not tile); the
chip's compiler is exercised by tests/test_tpu_compile.py, the chip by the
driver's check."""
import json
import subprocess
import sys

import pytest

import bench_rehearse as br

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("workload,metric", [
    ("tiny-train", "train_tokens_s_chip"),
    ("tiny-train-s2mp2", "train_tokens_s_chip"),   # four virtual devices
    ("tiny-serve", "ttft_p90_ms"),
])
def test_runner_rehearsal(workload, metric):
    cell, result, line = br.rehearse(workload, seconds=1.0)
    assert LINE_KEYS <= set(line)
    assert list(line)[-1] == "compared"         # the compared numbers last
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == set(cell.end_to_end_names())
    assert line["metrics"][metric]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["device"]["count"] == cell.chips
    assert line["compiles_in_window"] == 0
    for c in line["compared"].values():
        assert c["value"] <= c["limit"]
    json.dumps(line)                             # one JSON object


@pytest.mark.parametrize("workload", ["tiny-train", "tiny-serve"])
def test_traced_line_reports_layer_metrics(workload, monkeypatch):
    """--trace 1: the per-layer metrics whose reader finds something, the
    device's busy and window seconds, and a breakdown. The profiler's trace
    is stood in for by a hand-built one (the CPU has no TPU plane)."""
    br.stand_in_tracer(monkeypatch)
    cell, result, line = br.rehearse(workload, seconds=1.0, trace=True)
    kind = workload.split("-")[1]
    assert f"{kind}.device_idle_share" in line["metrics"]
    assert f"{kind}.step_mfu" in line["metrics"]
    assert 0 < line["metrics"][f"{kind}.step_mfu"]["value"] < 100
    # a reader with nothing to read is left out, never 0
    assert "train.flash_attention_roofline" not in line["metrics"]
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["device_ops"] and line["breakdown"]["idle_gaps"]
    assert set(line["metrics"]) <= set(cell.per_layer_names())


def test_run_py_refuses_a_cpu():
    """The command itself: no TPU, so exit code not 0 and no result line."""
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mistral7b-train-4k", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=br.REPO, capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("key,missing", [
    ("program", "benchmark/programs/nowhere.py"),
    ("step_work", "benchmark/work/nowhere.py"),
    ("published_as", "benchmark/published/nowhere.json"),
])
def test_run_py_names_the_file_a_configuration_lacks(key, missing,
                                                     monkeypatch, capsys):
    """A configuration that names a file the benchmark does not have: exit
    code 1, no result line, and the key and the file by name (before the
    look for a chip, so the CPU shows it)."""
    from benchmark import harness
    from benchmark import run as run_mod

    cell = harness.Cell.find("mistral7b-serve-chat")
    cell.config[key] = "nowhere"
    monkeypatch.setattr(harness.Cell, "find",
                        classmethod(lambda cls, *a, **kw: cell))
    assert run_mod.main(["--workload", cell.name, "--seed", "1",
                         "--seconds", "1"]) == 1
    said = capsys.readouterr()
    assert said.out == ""
    assert missing in said.err and f'"{key}"' in said.err
