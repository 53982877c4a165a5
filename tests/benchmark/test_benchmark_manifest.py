"""BENCHMARK.json against the files it names and the contract's limits on
names; and that a configuration, a traffic mix and a layer metric are each
picked up as files plus one manifest entry, with no edit to a file that is
there."""
import json
import os
import re
import shutil

import pytest

import bench_rehearse as br
from benchmark import harness, reduce_trace as rt

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = {"hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_experts_per_tok", "sliding_window", "state_size", "expand"}
MANIFESTS = [br.REPO, br.DATA]


def manifest_of(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def bench_dir_of(root):
    return harness.BENCH_DIR if root == br.REPO else root


@pytest.mark.parametrize("root", MANIFESTS, ids=["real", "rehearsal"])
def test_names_units_and_keys(root):
    m = manifest_of(root)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    assert (2 + 14 * 24) * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    metrics = m["end_to_end"] + m["per_layer"]
    names = [x["name"] for x in metrics]
    assert len(names) == len(set(names))
    for group in (m["configs"], m["workloads"], metrics):
        for x in group:
            assert NAME.match(x["name"]), x["name"]
    for x in metrics:
        assert UNIT.match(x["unit"]), x
        assert x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.1
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(x["layer"]) <= 200 and "\n" not in x["layer"]
    assert "setup_s" in [x["name"] for x in m["end_to_end"]]
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    if root == br.REPO:
        assert len(json.dumps(m)) < 64 * 1024
        assert m["paths"] == ["benchmark", "tests/benchmark"]
        assert m["command"] == ["python3", "benchmark/run.py"]


@pytest.mark.parametrize("root", MANIFESTS, ids=["real", "rehearsal"])
def test_every_cell_finds_its_files(root):
    m = manifest_of(root)
    used = {w["config"] for w in m["workloads"]}
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))
    for c in m["configs"]:
        assert c["name"] in used, f"configuration {c['name']} has no cell"
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and len(c["source"]) <= 200
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert cfg["entry"] in ("train", "serve")
        for key in ("assumed", "departures", "deployment", "published"):
            assert key in cfg
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg["published"]
            assert key not in WIDTHS           # a width is never cut
            assert not key.endswith(("_dim", "_rank"))
        assert cfg["assumed"]["max_context"] <= 4096
    for w in m["workloads"]:
        cell = harness.Cell.find(w["name"], root=root,
                                 bench_dir=bench_dir_of(root))
        assert cell.traffic["kind"] in ("train_batches", "open_loop")
        assert cell.limits and all(v >= 0 for v in cell.limits.values())
        harness.load_module("runners", cell.config["entry"])
        harness.load_module("reference", cell.config["reference"])
        assert "setup_s" in cell.end_to_end_names()
        assert len(cell.end_to_end_names()) >= 2
        assert len(cell.per_layer_names()) >= 1
        if cell.traffic["kind"] == "open_loop" and root == br.REPO:
            assert cell.traffic["rate_per_s"] > 0   # a number, from a sweep


def test_real_widths_are_the_published_ones():
    published = {"hidden_size": 4096, "intermediate_size": 14336,
                 "num_attention_heads": 32, "num_key_value_heads": 8,
                 "vocab_size": 32000, "sliding_window": 4096,
                 "rope_theta": 10000.0, "max_position_embeddings": 32768}
    for c in manifest_of(br.REPO)["configs"]:
        with open(os.path.join(br.REPO, c["file"])) as f:
            cfg = json.load(f)
        for k, v in published.items():
            assert cfg[k] == v, (c["name"], k)


@pytest.mark.parametrize("root", MANIFESTS, ids=["real", "rehearsal"])
def test_layer_metrics_agree_with_their_files(root):
    m = manifest_of(root)
    cells = {w["name"]: w for w in m["workloads"]}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    for x in m["per_layer"]:
        spec = harness.load_json("layer_metrics", x["name"] + ".json")
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == x[key], (x["name"], key)
        reader = harness.load_module("readers", spec["reader"])
        assert callable(reader.read)
        if "work" in spec.get("params", {}):
            harness.load_module("work", spec["params"]["work"])
        assert x["moves"] in e2e
        for name in x["workloads"]:
            cell = harness.Cell.find(name, root=root,
                                     bench_dir=bench_dir_of(root))
            # each of its cells reports the end-to-end metric it moves
            assert x["moves"] in cell.end_to_end_names(), (x["name"], name)
            assert cell.config["entry"] == spec["applies_to"]["entry"]
            assert cells[name]["chips"] >= spec["applies_to"]["min_chips"]
        if "roofline" in x["name"] or "mfu" in x["name"]:
            assert x["unit"] == "%"
    # the kernels' rooflines stand beside the whole step's share
    for x in m["per_layer"]:
        if x["name"].endswith("_roofline"):
            assert any("mfu" in y["name"] and y["moves"] == x["moves"]
                       and set(x["workloads"]) <= set(y["workloads"])
                       for y in m["per_layer"]), x["name"]


def test_new_files_are_picked_up_with_no_edit_elsewhere(tmp_path):
    """A configuration, a traffic mix and a layer metric that uses an
    existing reader: three new files (and the cell's limits), one entry
    each in the manifest, and the harness finds them by name."""
    root = str(tmp_path)
    shutil.copytree(br.DATA, root, dirs_exist_ok=True)
    shutil.copytree(os.path.join(harness.BENCH_DIR, "layer_metrics"),
                    os.path.join(root, "layer_metrics"))
    with open(os.path.join(root, "configs", "tiny-train.json")) as f:
        cfg = json.load(f)
    cfg.update(name="added-train", num_hidden_layers=1)
    with open(os.path.join(root, "configs", "added-train.json"), "w") as f:
        json.dump(cfg, f)
    mix = harness.load_json("traffic", "tiny-batches.json", bench_dir=root)
    mix["batch"] = 1
    with open(os.path.join(root, "traffic", "added-batches.json"), "w") as f:
        json.dump(mix, f)
    shutil.copy(os.path.join(root, "limits", "tiny-train.json"),
                os.path.join(root, "limits", "added-cell.json"))
    spec = harness.load_json("layer_metrics", "train.device_idle_share.json")
    spec.update(name="added.step_host_ms", unit="ms", reader="span_host_ms",
                params={"annotation": "bench.trainer_step"})
    with open(os.path.join(root, "layer_metrics", "added.step_host_ms.json"),
              "w") as f:
        json.dump(spec, f)
    m = manifest_of(root)
    m["configs"].append({"name": "added-train", "source": cfg["source"],
                         "file": "configs/added-train.json",
                         "reduced": cfg["reduced"], "why": "test"})
    m["workloads"].append({"name": "added-cell", "config": "added-train",
                           "traffic": "added-batches", "chips": 1,
                           "why": "test"})
    m["end_to_end"][0]["workloads"].append("added-cell")
    m["per_layer"].append({**{k: spec[k] for k in (
        "name", "unit", "better", "source", "layer", "moves")},
        "workloads": ["added-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)

    cell = harness.Cell.find("added-cell", root=root, bench_dir=root)
    assert cell.config["num_hidden_layers"] == 1
    assert cell.traffic["batch"] == 1
    assert cell.per_layer_names() == ["added.step_host_ms"]
    result = harness.RunResult(attempted=1, failed=0, end_to_end={},
                               compared={}, trace=br.synthetic_trace())
    got = harness.per_layer_metrics(cell, result, {}, bench_dir=root)
    assert got == {"added.step_host_ms": {
        "value": pytest.approx(10.0), "unit": "ms"}}
    assert rt.window_of(result.trace)[0] == 0


def test_traffic_generator_orders_fixed_sets():
    """Every seed's window holds the same lengths and gaps in the same
    ring, begun at another place; the same seed gives the same traffic;
    seeds pass 2**31."""
    from benchmark import traffic_gen

    mix = harness.load_json("traffic", "chat-open-0.8knee.json")
    mix["rate_per_s"] = 2.0
    phases = [5.0, 30.0, 10.0]
    a = traffic_gen.open_loop(mix, 5, 32000, phases)
    b = traffic_gen.open_loop(mix, 2 ** 31 + 9, 32000, phases)
    again = traffic_gen.open_loop(mix, 5, 32000, phases)
    assert [r.phase for r in a] == [0] * 10 + [1] * 60 + [2] * 20
    assert [r.prompt for r in a] == [r.prompt for r in again]
    assert [r.due_s for r in a] != [r.due_s for r in b]
    assert all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))
    wa, wb = ([r for r in x if r.phase == 1] for x in (a, b))
    assert wa[0].due_s == wb[0].due_s == 5.0 and wa[-1].due_s < 35.0
    for pick in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(pick, wa)) == sorted(map(pick, wb))
    la, lb = ([(len(r.prompt), r.max_new) for r in w] for w in (wa, wb))
    assert la != lb and any(la[k:] + la[:k] == lb for k in range(60))
    gaps = [sorted(round(y.due_s - x.due_s, 9) for x, y in zip(w, w[1:]))
            for w in (wa, wb)]
    assert len(set(gaps[0]) & set(gaps[1])) >= 57    # all but the last gap
    lens = sorted(len(r.prompt) for r in wa)
    assert lens[0] >= 32 and lens[-1] <= 1024 and 200 <= lens[30] <= 300
    mix.update(shared_prefix_len=64, shared_prefix_groups=2)
    c = traffic_gen.open_loop(mix, 5, 32000, phases)
    assert c[0].prompt[:64] == c[2].prompt[:64] != c[1].prompt[:64]
    assert all(len(r.prompt) >= 65 for r in c)
    train = harness.load_json("traffic", "train-4k-b2.json")
    x = traffic_gen.train_batches(train, 2 ** 31 + 9, 32000)
    assert len(x) == 8 and x[0][0].shape == (2, 4096)
    assert (x[0][1][:, :-1] == x[0][0][:, 1:]).all()
    assert len({bytes(row) for ids, _ in x for row in ids}) == 16
