"""The repository's records against the tree: the documents that describe
the system as it is name only files that exist, `PERF.md` names everything
`BENCHMARK.json` measures, every file under `tools/` has a reader, and
`tools/check_op_bench.py` (the relative gate between two `op_bench.py`
runs) does what its usage line says. No test here reads a clock.
"""
import fnmatch
import glob
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the account of the system as it is; PERF.md's sections 5-7, CHANGES.md
# and ROADMAP.md are history and may name what is gone
DOCUMENTS = {
    "README": "README.md",
    "COVERAGE": "COVERAGE.md",
    "RUNBOOK": "paddle_tpu/distributed/resilience/RUNBOOK.md",
    "verify-skill": ".claude/skills/verify/SKILL.md",
    "PERF-1-4": "PERF.md",
}


def read(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return f.read()


def document(key):
    text = read(DOCUMENTS[key])
    if key == "PERF-1-4":
        text = text[:text.index("\n## 5. ")]
    return text


def ignored_patterns():
    return [line.strip().rstrip("/").replace("**/", "")
            for line in read(".gitignore").splitlines()
            if line.strip() and not line.startswith("#")]


def is_ignored(path, patterns):
    """Made by a build or a run and listed in `.gitignore`: not in a fresh
    checkout, so a document may name it."""
    parts = path.rstrip("/").split("/")
    return any(fnmatch.fnmatch("/".join(parts[:n]), pat)
               or fnmatch.fnmatch(parts[n - 1], pat)
               for pat in patterns for n in range(1, len(parts) + 1))


def top_level_dirs(patterns):
    return sorted(d for d in os.listdir(ROOT)
                  if os.path.isdir(os.path.join(ROOT, d)) and d != ".git"
                  and not is_ignored(d, patterns))


def named_paths(text, tops):
    """Words inside backticks that begin with a top-level directory or are
    a bare `*.py`, cut at `::test`, `:function` and trailing punctuation."""
    starts = tuple(d + "/" for d in tops)
    found = set()
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = word.split("::")[0].strip("\"'(),;")
            word = re.sub(r":[A-Za-z_][\w.]*$", "", word).rstrip(".:")
            if any(c in word for c in "<>{}…$"):
                continue                     # a placeholder, not a name
            if word.startswith(starts) \
                    or re.fullmatch(r"[A-Za-z_]\w*\.py", word):
                found.add(word)
    return found


@pytest.mark.parametrize("doc", sorted(DOCUMENTS))
def test_documents_name_only_files_that_exist(doc):
    patterns = ignored_patterns()
    paths = named_paths(document(doc), top_level_dirs(patterns))
    assert paths, "the document names no file at all: the reader is broken"
    # a bare `name.py` is a root file, or shorthand for a module below
    missing = sorted(
        p for p in paths
        if not glob.glob(os.path.join(ROOT, p.rstrip("/")))
        and not ("/" not in p and glob.glob(
            os.path.join(ROOT, "*", "**", p), recursive=True))
        and not is_ignored(p, patterns))
    assert not missing, f"{DOCUMENTS[doc]} names files that are gone"


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_perf_md_names_what_the_benchmark_measures(kind):
    perf = read("PERF.md")
    names = [e["name"] for e in json.loads(read("BENCHMARK.json"))[kind]]
    assert names
    assert not [n for n in names if n not in perf]


def tools():
    return sorted(f for f in os.listdir(os.path.join(ROOT, "tools"))
                  if f.endswith(".py"))


@pytest.fixture(scope="module")
def readers():
    """Tests, the package, `chip_smoke.py` and the five documents: where a
    tool has to be named to count as used."""
    chunks = [document(k) for k in DOCUMENTS]
    chunks.append(read("chip_smoke.py"))
    for top in ("tests", "paddle_tpu"):
        for path in glob.glob(os.path.join(ROOT, top, "**", "*.py"),
                              recursive=True):
            if os.path.abspath(path) != os.path.abspath(__file__):
                chunks.append(read(path))
    return "\n".join(chunks)


@pytest.mark.parametrize("tool", tools())
def test_every_tool_has_a_reader(tool, readers):
    stem = tool[:-3]
    assert re.search(rf"\b{re.escape(stem)}\b", readers), \
        f"tools/{tool} is named by no test, module or document"


# -- tools/check_op_bench.py ---------------------------------------------

def check_op_bench(tmp_path, base, cur, *options):
    paths = []
    for name, ops in (("base.json", base), ("cur.json", cur)):
        path = tmp_path / name
        path.write_text(json.dumps({"device": "none", "ops": ops}))
        paths.append(str(path))
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "check_op_bench.py"),
         *paths, *options], capture_output=True, text=True, timeout=60)


OPS = {"matmul": 1.0e-4, "softmax": 2.0e-5, "eager:matmul_add_fwd": 5.0e-5}


@pytest.mark.parametrize("case", ["against_itself", "one_op_1.6x_slower",
                                  "threshold_option"])
def test_check_op_bench_is_a_relative_gate(tmp_path, case):
    slower = dict(OPS, softmax=OPS["softmax"] * 1.6)
    if case == "against_itself":
        done = check_op_bench(tmp_path, OPS, OPS)
        assert done.returncode == 0 and "PASS" in done.stdout
        # a run gates itself whatever its rows are: no absolute bar, and
        # no row is required
        assert check_op_bench(tmp_path, {"matmul": 3.0},
                              {"matmul": 3.0}).returncode == 0
    elif case == "one_op_1.6x_slower":
        done = check_op_bench(tmp_path, OPS, slower)
        assert done.returncode == 1
        assert "softmax" in done.stdout and "SLOWER" in done.stdout
        assert "FAIL: 1 op(s)" in done.stdout
    else:
        assert check_op_bench(tmp_path, OPS, slower,
                              "--threshold=1.7").returncode == 0
        assert check_op_bench(tmp_path, OPS, slower,
                              "--threshold=1.15").returncode == 1
