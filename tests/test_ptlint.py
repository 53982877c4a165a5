"""Fixture tests for the ptlint rule suite (paddle_tpu/analysis/).

Every rule ID gets a known-bad snippet proving a true positive and a
known-good snippet proving a clean pass — including the fixture
reproducing the pre-fix varlen floor-truncation shape (PT301/PT302:
``block = min(512, seq)`` + ``grid = seq // block`` silently dropped
the trailing tokens of 640/768/896 packs).  Engine mechanics
(suppressions, baseline, reporters, select) are covered at the end.
"""
import json
import textwrap

import pytest

from paddle_tpu.analysis import engine


def lint(tmp_path, src, name="mod.py", select=None, baseline=None):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return engine.run([str(p)], select=select, baseline=baseline)


def ids(report):
    return [f.rule_id for f in report.findings]


# ---------------------------------------------------------------------------
# PT1xx — trace safety
# ---------------------------------------------------------------------------

def test_pt101_print_in_traced_function(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.jit import to_static

        @to_static
        def step(x):
            print("loss", x)
            return x * 2
    """)
    assert "PT101" in ids(rep)


def test_pt101_clean_outside_traced_function(tmp_path):
    rep = lint(tmp_path, """
        def plain(x):
            print("not traced", x)
            return x
    """)
    assert "PT101" not in ids(rep)


def test_pt102_wallclock_frozen_at_trace(tmp_path):
    rep = lint(tmp_path, """
        import time
        from paddle_tpu.jit import to_static

        @to_static
        def step(x):
            t0 = time.time()
            return x + t0
    """)
    assert "PT102" in ids(rep)


def test_pt103_host_rng_in_traced_function(tmp_path):
    rep = lint(tmp_path, """
        import random
        import paddle

        @paddle.jit.to_static
        def step(x):
            return x * random.random()
    """)
    assert "PT103" in ids(rep)


def test_pt103_traced_prng_is_clean(tmp_path):
    rep = lint(tmp_path, """
        import jax
        from paddle_tpu.jit import to_static

        @to_static
        def step(x, key):
            return x + jax.random.normal(key, x.shape)
    """)
    assert "PT103" not in ids(rep)


def test_pt104_nonlocal_mutation(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.jit import to_static

        def make_step():
            calls = 0

            @to_static
            def step(x):
                nonlocal calls
                calls = calls + 1
                return x

            return step
    """)
    assert "PT104" in ids(rep)


def test_pt105_numpy_call_breaks_trace(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.jit import to_static

        @to_static
        def step(x):
            host = x.numpy()
            return host.sum()
    """)
    assert "PT105" in ids(rep)


def test_pt106_float_of_tensor_argument(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.jit import to_static

        @to_static
        def step(loss):
            return float(loss) * 2
    """)
    assert "PT106" in ids(rep)


def test_pt107_data_dependent_branch(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.jit import to_static

        @to_static
        def step(x):
            if x.sum() > 0:
                return x
            return -x
    """)
    assert "PT107" in ids(rep)


def test_pt1xx_reachability_is_transitive(tmp_path):
    """A helper CALLED from a to_static function is traced too."""
    rep = lint(tmp_path, """
        from paddle_tpu.jit import to_static

        def helper(x):
            print(x)
            return x

        @to_static
        def step(x):
            return helper(x)
    """)
    assert "PT101" in ids(rep)


def test_pt1xx_clean_traced_function(tmp_path):
    rep = lint(tmp_path, """
        import jax.numpy as jnp
        from paddle_tpu.jit import to_static

        @to_static
        def step(x, y):
            z = jnp.where(x > 0, x, -x)
            return z + y
    """)
    assert not [i for i in ids(rep) if i.startswith("PT1")]


# ---------------------------------------------------------------------------
# PT2xx — SPMD collective ordering
# ---------------------------------------------------------------------------

def test_pt201_unmatched_collective_under_rank_branch(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.distributed import collective as dist

        def sync(t, g):
            if dist.get_rank() == 0:
                dist.broadcast(t, src=0, group=g)
    """)
    assert "PT201" in ids(rep)


def test_pt201_mirrored_branches_are_clean(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.distributed import collective as dist

        def exchange(t, rank, g):
            if rank == 0:
                dist.send(t, dst=1, group=g)
            else:
                dist.recv(t, src=0, group=g)
    """)
    assert "PT201" not in ids(rep)


def test_pt201_unconditional_collective_is_clean(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.distributed import collective as dist

        def sync(t, g):
            dist.all_reduce(t, group=g)
    """)
    assert not [i for i in ids(rep) if i.startswith("PT2")]


def test_pt202_send_recv_group_mismatch(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.distributed import collective as dist

        def exchange(t, rank, g_fwd, g_bwd):
            if rank == 0:
                dist.send(t, dst=1, group=g_fwd)
            else:
                dist.recv(t, src=0, group=g_bwd)
    """)
    assert "PT202" in ids(rep)


def test_pt202_matching_groups_clean(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.distributed import collective as dist

        def exchange(t, rank, g):
            if rank == 0:
                dist.send(t, dst=1, group=g)
            else:
                dist.recv(t, src=0, group=g)
    """)
    assert "PT202" not in ids(rep)


# ---------------------------------------------------------------------------
# PT3xx — Pallas grid contracts
# ---------------------------------------------------------------------------

VARLEN_PREFIX_BUG = """
    import jax
    from jax.experimental import pallas as pl

    def kernel(q_ref, o_ref):
        o_ref[0] = q_ref[0]

    def fwd(q):
        bh, sq, d = q.shape
        block_q = min(512, sq)      # merely FITS — 640 -> grid of 1
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            grid=(bh, sq // block_q),
            in_specs=[pl.BlockSpec((1, block_q, d),
                                   lambda i, j: (i, j, 0))],
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda i, j: (i, j, 0)),
        )(q)
"""


def test_pt301_varlen_prefix_floor_truncation_flagged(tmp_path):
    """The EXACT pre-fix varlen-attention shape: min-clamped block +
    `sq // block_q` grid, no divisibility guard anywhere. 640/768/896
    packs silently dropped their tails; ptlint must flag it."""
    rep = lint(tmp_path, VARLEN_PREFIX_BUG)
    assert "PT301" in ids(rep)
    assert "PT302" in ids(rep)


def test_pt301_guarded_selector_is_clean(tmp_path):
    """The POST-fix varlen shape: the block comes from a selector that
    proves divisibility (`s % b == 0`), threaded through a parameter."""
    rep = lint(tmp_path, """
        import jax
        from jax.experimental import pallas as pl

        def kernel(q_ref, o_ref):
            o_ref[0] = q_ref[0]

        def _block(s):
            for b in (512, 256, 128):
                if s % b == 0:
                    return b
            return 0

        def _fwd(q, block_q):
            bh, sq, d = q.shape
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                grid=(bh, sq // block_q),
                in_specs=[pl.BlockSpec((1, block_q, d),
                                       lambda i, j: (i, j, 0))],
                out_specs=pl.BlockSpec((1, block_q, d),
                                       lambda i, j: (i, j, 0)),
            )(q)

        def fwd(q):
            return _fwd(q, _block(q.shape[1]))
    """)
    assert "PT301" not in ids(rep)


def test_pt302_modulo_fallback_is_clean(tmp_path):
    """rms_norm's shape: min clamp WITH an `n % block` guard and a
    reference fallback — clean."""
    rep = lint(tmp_path, """
        import jax
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def ref(x):
            return x

        def fwd(x):
            n, h = x.shape
            block = min(256, n)
            if n % block != 0:
                return ref(x)
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct((n, h), x.dtype),
                grid=(n // block,),
                in_specs=[pl.BlockSpec((block, h), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((block, h), lambda i: (i, 0)),
            )(x)
    """)
    assert "PT301" not in ids(rep)
    assert "PT302" not in ids(rep)


def test_pt303_direct_renamed_pltpu_attr(tmp_path):
    rep = lint(tmp_path, """
        from jax.experimental.pallas import tpu as pltpu

        def params():
            return pltpu.TPUCompilerParams(
                dimension_semantics=("parallel",))
    """)
    assert "PT303" in ids(rep)


def test_pt303_current_name_is_clean(tmp_path):
    rep = lint(tmp_path, """
        from jax.experimental.pallas import tpu as pltpu

        def params():
            return pltpu.CompilerParams(
                dimension_semantics=("parallel",))
    """)
    assert "PT303" not in ids(rep)


# ---------------------------------------------------------------------------
# PT4xx — registry consistency
# ---------------------------------------------------------------------------

def test_pt401_duplicate_registration_same_module(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.ops.registry import register

        def foo(x):
            return x

        def foo2(x):
            return x * 2

        register("foo", foo)
        register("foo", foo2)
    """)
    assert "PT401" in ids(rep)


def test_pt401_duplicate_across_modules(tmp_path):
    (tmp_path / "a.py").write_text(textwrap.dedent("""
        from paddle_tpu.ops.registry import register

        def relu(x):
            return x

        register("relu", relu)
    """))
    (tmp_path / "b.py").write_text(textwrap.dedent("""
        from paddle_tpu.ops.registry import register

        def relu(x):
            return x

        register("relu", relu)
    """))
    rep = engine.run([str(tmp_path)])
    assert "PT401" in ids(rep)


def test_pt401_loop_registration_clean(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.ops import registry

        __all__ = ["alpha", "beta"]

        def alpha(x):
            return x

        def beta(x):
            return x + 1

        for _n in __all__:
            registry.register(_n, globals()[_n], tags=("t",))
    """)
    assert "PT401" not in ids(rep)


def test_pt402_zero_arg_op_flagged(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.ops.registry import register

        def broken():
            return 1

        register("broken", broken)
    """)
    assert "PT402" in ids(rep)


def test_pt402_required_kwonly_flagged_via_loop(tmp_path):
    """The globals()[_n] loop idiom resolves each op by name."""
    rep = lint(tmp_path, """
        from paddle_tpu.ops import registry

        __all__ = ["ok_op", "kw_op"]

        def ok_op(x, axis=0):
            return x

        def kw_op(x, *, mode):
            return x

        for _n in __all__:
            registry.register(_n, globals()[_n])
    """)
    flagged = [f for f in rep.findings if f.rule_id == "PT402"]
    assert len(flagged) == 1 and "kw_op" in flagged[0].message


def test_pt402_normal_signatures_clean(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.ops.registry import register

        def add(x, y, name=None):
            return x + y

        register("add", add)
    """)
    assert "PT402" not in ids(rep)


def _metrics_project(tmp_path, metric_name):
    (tmp_path / "tools").mkdir(exist_ok=True)
    (tmp_path / "tools" / "trace_report.py").write_text(textwrap.dedent("""
        KNOWN_METRICS = ("app/known_count", "fam/*_bytes")
    """))
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "mod.py").write_text(textwrap.dedent(f"""
        from profiler import metrics as _metrics

        _m = _metrics.counter("{metric_name}")
    """))
    return engine.run([str(pkg)])


def test_pt403_unknown_metric_flagged(tmp_path):
    rep = _metrics_project(tmp_path, "app/typo_count")
    assert "PT403" in ids(rep)


def test_pt403_known_and_pattern_metrics_clean(tmp_path):
    assert "PT403" not in ids(_metrics_project(tmp_path,
                                               "app/known_count"))
    assert "PT403" not in ids(_metrics_project(tmp_path,
                                               "fam/send_bytes"))


# ---------------------------------------------------------------------------
# PT5xx — error surfacing in distributed/
# ---------------------------------------------------------------------------

def _lint_distributed(tmp_path, src):
    """PT5xx is scoped to files under a distributed/ directory."""
    d = tmp_path / "distributed"
    d.mkdir(exist_ok=True)
    p = d / "mod.py"
    p.write_text(textwrap.dedent(src))
    return engine.run([str(p)])


SWALLOWED = """
    def beat(store):
        try:
            store.set("hb", "1")
        except Exception:
            pass
"""


def test_pt501_bare_except_flagged(tmp_path):
    rep = _lint_distributed(tmp_path, """
        def loop(store):
            try:
                store.set("hb", "1")
            except:
                pass
    """)
    assert "PT501" in ids(rep)


def test_pt502_swallowed_exception_flagged(tmp_path):
    rep = _lint_distributed(tmp_path, SWALLOWED)
    assert "PT502" in ids(rep)


def test_pt502_continue_body_flagged(tmp_path):
    rep = _lint_distributed(tmp_path, """
        def scan(items):
            for it in items:
                try:
                    it.load()
                except Exception:
                    continue
    """)
    assert "PT502" in ids(rep)


def test_pt502_counted_error_is_clean(tmp_path):
    rep = _lint_distributed(tmp_path, """
        from paddle_tpu.profiler import metrics as _metrics

        def beat(store):
            try:
                store.set("hb", "1")
            except Exception:
                _metrics.inc("elastic/heartbeat_errors")
    """)
    assert "PT502" not in ids(rep)


def test_pt502_fallback_value_is_clean(tmp_path):
    rep = _lint_distributed(tmp_path, """
        def probe(store):
            try:
                return float(store.get("hb"))
            except Exception:
                return None
    """)
    assert "PT502" not in ids(rep)


def test_pt502_narrow_except_is_clean(tmp_path):
    rep = _lint_distributed(tmp_path, """
        def close(sock):
            try:
                sock.close()
            except OSError:
                pass
    """)
    assert "PT502" not in ids(rep)


def test_pt5xx_out_of_scope_path_is_clean(tmp_path):
    # same bad code OUTSIDE a distributed/ directory: not our contract
    rep = lint(tmp_path, SWALLOWED)
    assert not [i for i in ids(rep) if i.startswith("PT5")]


SLEEP_RETRY = """
    import time

    def connect(sock, addr):
        while True:
            try:
                sock.connect(addr)
                return
            except OSError:
                time.sleep(0.2)
"""


def test_pt503_constant_sleep_retry_flagged(tmp_path):
    rep = _lint_distributed(tmp_path, SLEEP_RETRY)
    assert "PT503" in ids(rep)


def test_pt503_backoff_helper_is_clean(tmp_path):
    rep = _lint_distributed(tmp_path, """
        import time
        from paddle_tpu.distributed.resilience.backoff import delay

        def connect(sock, addr):
            attempt = 0
            while True:
                try:
                    sock.connect(addr)
                    return
                except OSError:
                    attempt += 1
                    time.sleep(delay(attempt))
    """)
    assert "PT503" not in ids(rep)


def test_pt503_poll_loop_without_handler_is_clean(tmp_path):
    # a pure poll loop (no exception handler) is not a retry loop
    rep = _lint_distributed(tmp_path, """
        import time

        def wait_ready(store):
            while not store.ready():
                time.sleep(0.5)
    """)
    assert "PT503" not in ids(rep)


def test_pt503_sleep_in_nested_def_is_clean(tmp_path):
    # the sleep belongs to an inner function's own context, not the loop
    rep = _lint_distributed(tmp_path, """
        import time

        def build(workers):
            for w in workers:
                try:
                    w.start()
                except OSError:
                    pass

                def later():
                    time.sleep(1.0)
                w.on_exit(later)
    """)
    assert "PT503" not in ids(rep)


def test_pt503_out_of_scope_is_clean(tmp_path):
    rep = lint(tmp_path, SLEEP_RETRY)
    assert "PT503" not in ids(rep)


# ---------------------------------------------------------------------------
# engine mechanics: suppression, baseline, reporters, select
# ---------------------------------------------------------------------------

def test_inline_suppression(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.jit import to_static

        @to_static
        def step(x):
            print(x)  # ptlint: disable=PT101
            return x
    """)
    assert "PT101" not in ids(rep)
    assert rep.suppressed == 1


def test_family_suppression(tmp_path):
    rep = lint(tmp_path, """
        from paddle_tpu.jit import to_static

        @to_static
        def step(x):
            print(x)  # ptlint: disable=PT1xx
            return x
    """)
    assert "PT101" not in ids(rep)


def test_file_level_suppression(tmp_path):
    rep = lint(tmp_path, """
        # ptlint: disable-file=PT1xx
        from paddle_tpu.jit import to_static

        @to_static
        def step(x):
            print(x)
            return float(x)
    """)
    assert not [i for i in ids(rep) if i.startswith("PT1")]
    assert rep.suppressed >= 2


def test_baseline_grandfathers_findings(tmp_path):
    src = """
        from paddle_tpu.jit import to_static

        @to_static
        def step(x):
            print(x)
            return x
    """
    # the baseline lives at the project root BEFORE the run (as the
    # committed one does) so finding paths anchor to its directory
    base = tmp_path / engine.BASELINE_NAME
    base.write_text('{"entries": []}')
    rep = lint(tmp_path, src)
    assert "PT101" in ids(rep)
    engine.write_baseline(str(base), rep.findings)
    rep2 = lint(tmp_path, src, baseline=str(base))
    assert "PT101" not in ids(rep2)
    assert [f.rule_id for f in rep2.baselined] == ["PT101"]
    assert rep2.exit_code == 0


def test_select_restricts_rules(tmp_path):
    rep = lint(tmp_path, VARLEN_PREFIX_BUG, select=["PT301"])
    assert set(ids(rep)) == {"PT301"}
    rep = lint(tmp_path, VARLEN_PREFIX_BUG, select=["PT3xx"])
    assert {"PT301", "PT302"} <= set(ids(rep))


def test_json_reporter_roundtrips(tmp_path):
    rep = lint(tmp_path, VARLEN_PREFIX_BUG)
    data = json.loads(engine.render_json(rep))
    assert data["files"] == 1
    assert {f["id"] for f in data["findings"]} >= {"PT301", "PT302"}
    txt = engine.render_text(rep)
    assert "PT301" in txt and "finding(s)" in txt


def test_all_rule_families_registered():
    rules = engine.all_rules()
    fams = {rid[:3] for rid in rules}
    assert {"PT1", "PT2", "PT3", "PT4", "PT5"} <= fams
    for r in rules.values():
        assert r.severity in ("error", "warning")
        assert r.scope in ("file", "project")


def test_cli_standalone_no_jax(tmp_path):
    """tools/ptlint.py runs without importing the framework (no jax),
    and exits nonzero on a bad file, zero on a clean one."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(VARLEN_PREFIX_BUG))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "ptlint.py"),
         str(bad), "--no-baseline"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "PT301" in r.stdout
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "ptlint.py"),
         str(good), "--no-baseline"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


def test_sarif_reporter_emits_valid_results(tmp_path):
    rep = lint(tmp_path, VARLEN_PREFIX_BUG)
    doc = json.loads(engine.render_sarif(rep))
    assert doc["version"] == "2.1.0"
    run0 = doc["runs"][0]
    assert run0["tool"]["driver"]["name"] == "ptlint"
    got = {r["ruleId"] for r in run0["results"]}
    assert {"PT301", "PT302"} <= got
    r0 = run0["results"][0]
    loc = r0["locations"][0]["physicalLocation"]
    assert loc["region"]["startLine"] >= 1
    assert loc["artifactLocation"]["uri"].endswith(".py")
    # every emitted result's rule is described in the driver
    described = {ru["id"] for ru in run0["tool"]["driver"]["rules"]}
    assert got <= described


def test_sarif_marks_baselined_as_suppressed(tmp_path):
    src = """
        from paddle_tpu.jit import to_static

        @to_static
        def step(x):
            print(x)
            return x
    """
    base = tmp_path / engine.BASELINE_NAME
    base.write_text('{"entries": []}')
    rep = lint(tmp_path, src)
    engine.write_baseline(str(base), rep.findings)
    rep2 = lint(tmp_path, src, baseline=str(base))
    doc = json.loads(engine.render_sarif(rep2))
    results = doc["runs"][0]["results"]
    assert results and all("suppressions" in r for r in results)


def test_update_baseline_prunes_stale_entries(tmp_path):
    """The staleness check used to only warn; --update-baseline now
    rewrites the baseline keeping exactly the entries that still match
    a live finding."""
    from paddle_tpu.analysis.main import main

    src = """
        from paddle_tpu.jit import to_static

        @to_static
        def step(x):
            print(x)
            return x
    """
    base = tmp_path / engine.BASELINE_NAME
    base.write_text('{"entries": []}')
    mod = tmp_path / "mod.py"
    mod.write_text(textwrap.dedent(src))
    rep = engine.run([str(mod)])
    assert ids(rep) == ["PT101"]
    # baseline = the live finding + a stale one for code long since fixed
    engine.write_baseline(str(base), rep.findings)
    data = json.loads(base.read_text())
    data["entries"].append({"id": "PT101", "path": "gone.py",
                            "context": "print(y)"})
    base.write_text(json.dumps(data))
    assert sum(engine.load_baseline(str(base)).values()) == 2

    rc = main([str(mod), "--baseline", str(base), "--update-baseline"])
    assert rc == 0
    kept = engine.load_baseline(str(base))
    assert sum(kept.values()) == 1
    assert all(path != "gone.py" for (_rid, path, _ctx) in kept)
    # and the pruned baseline still grandfathers the live finding
    rep2 = engine.run([str(mod)], baseline=str(base))
    assert ids(rep2) == [] and len(rep2.baselined) == 1
