"""BENCHMARK.json against the files it names and the contract's limits on
names; each configuration against the published sizes it names; and that a
configuration, a traffic mix, a layer metric and a whole second
architecture are each picked up as files plus manifest entries, with no
edit to a file that is there."""
import importlib.util
import json
import os
import re
import shutil
import sys

import pytest

import bench_rehearse as br
from benchmark import harness, reduce_trace as rt

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = {"hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_experts_per_tok", "sliding_window", "state_size", "expand"}
MANIFESTS = [br.REPO, br.DATA]
REAL_CONFIGS = os.path.join(harness.BENCH_DIR, "configs")
SECOND_ARCH = os.path.join(os.path.dirname(br.DATA), "second_arch")


def manifest_of(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def bench_dir_of(root):
    return harness.BENCH_DIR if root == br.REPO else root


def published_of(cfg, bench_dir):
    """The published sizes a configuration is held to: the file it names
    under `published_as`. A rehearsal's toy configuration need name none,
    and then stands for itself."""
    if "published_as" not in cfg:
        return cfg
    return harness.load_json("published", cfg["published_as"] + ".json",
                             bench_dir=bench_dir)


def check_cuts(cfg, published):
    """A width is never cut, and no context is longer than the published
    attention's: the window where the source has one, else its positions."""
    for key in cfg["reduced"]:
        assert NAME.match(key) and key in cfg["published"]
        assert key not in WIDTHS
        assert not key.endswith(("_dim", "_rank"))
    assert cfg["assumed"]["max_context"] <= (
        published.get("sliding_window")
        or published["max_position_embeddings"])


def check_against_published(cfg, bench_dir):
    """Every key of the source's config stands in the configuration with
    the published value, unless `reduced` lists it, and then `published`
    keeps the source's value."""
    published = dict(published_of(cfg, bench_dir))
    assert cfg["source"] == published.pop("source")
    for key, value in published.items():
        kept = cfg["published"] if key in cfg["reduced"] else cfg
        assert kept[key] == value, key
    assert set(cfg["reduced"]) <= set(published)
    check_cuts(cfg, published)


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
        # the runner, the program and the step's work it names all load
        assert callable(harness.load_module("runners", cfg["entry"]).run)
        if "program" in cfg:
            assert callable(harness.load_module(
                "programs", cfg["program"]).build_engine)
        assert callable(harness.load_module("work", cfg["step_work"]).flops)
        for key in ("assumed", "departures", "deployment", "published"):
            assert key in cfg
        check_cuts(cfg, published_of(cfg, bench_dir_of(root)))
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


@pytest.mark.parametrize("file", sorted(os.listdir(REAL_CONFIGS)))
def test_real_widths_are_the_published_ones(file):
    """One case a file under `benchmark/configs/`, the file-only ones
    among them: each names its published sizes and keeps to them."""
    with open(os.path.join(REAL_CONFIGS, file)) as f:
        cfg = json.load(f)
    assert "published_as" in cfg, "a real configuration names its source's"
    check_against_published(cfg, harness.BENCH_DIR)


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
            if spec["reader"] == "step_mfu":     # counted by its own file
                assert "work" not in spec["params"]
                harness.load_module("work", cell.config["step_work"])
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


def test_a_second_architecture_is_files_alone(tmp_path, monkeypatch):
    """What a `model_config` PR adds for an architecture the benchmark has
    not run, and no edit to a file that is there (`second_arch/` holds one
    of each, laid over a copy of the rehearsal's tree in `tmp_path`):

      benchmark/configs/<config>.json      the sizes as run, under the
                                           source's own key names, with
                                           `program`, `reference`,
                                           `step_work`, `published_as`
      benchmark/published/<model>.json     the source's config.json and URL
      benchmark/programs/<program>.py      build_engine(config, seed)
      benchmark/reference/<reference>.py   logits_at(...), plain float32
      benchmark/work/<step_work>.py        flops(model, stats) of its step
      benchmark/traffic/<traffic>.json     where no mix that is there fits
      benchmark/limits/<cell>.json         from readings on the chip
      BENCHMARK.json                       one entry in `configs` and in
                                           `workloads`, the cell's name in
                                           the `workloads` of its metrics

    (and its control and faults as a test under `tests/benchmark/`). The
    configuration here shares no size key with Mistral's, and its rope base
    is one the `paged_causal_lm` program refuses: the serving runner drives
    it to `correct`, `serve.step_mfu` is counted by ITS work file (the
    other would find none of its keys), and this file's tests of a
    manifest pass on the tree."""
    root = str(tmp_path)
    shutil.copytree(br.DATA, root, dirs_exist_ok=True)
    shutil.copytree(SECOND_ARCH, root, dirs_exist_ok=True)
    for kind in ("programs", "reference", "work"):
        for file in os.listdir(os.path.join(root, kind)):
            name = f"benchmark.{kind}.{file[:-len('.py')]}"
            spec = importlib.util.spec_from_file_location(
                name, os.path.join(root, kind, file))
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            monkeypatch.setitem(sys.modules, name, module)
    m = manifest_of(root)
    with open(os.path.join(root, "manifest_entries.json")) as f:
        entries = json.load(f)
    m["configs"].append(entries["config"])
    m["workloads"].append(entries["workload"])
    for metric in m["end_to_end"] + m["per_layer"]:
        if entries["reports_what"] in metric.get("workloads", []):
            metric["workloads"].append(entries["workload"]["name"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)

    cell = harness.Cell.find("alt-lm-chat", root=root, bench_dir=root)
    mistral = harness.load_json("published", "mistral-7b-v0.1.json")
    assert set(cell.config) & set(mistral) == {
        "source", "initializer_range", "max_position_embeddings"}
    assert cell.config["rope_base"] != mistral["rope_theta"]
    check_against_published(cell.config, root)
    test_names_units_and_keys(root)
    test_every_cell_finds_its_files(root)
    test_layer_metrics_agree_with_their_files(root)

    br.stand_in_tracer(monkeypatch)
    cell, result, line = br.rehearse("alt-lm-chat", seconds=1.0, trace=True,
                                     root=root)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["compiles_in_window"] == 0
    assert 0 < line["metrics"]["serve.step_mfu"]["value"] < 100
    assert "serve.device_idle_share" in line["metrics"]


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
