"""The MiniCPM-SALA cell's rehearsal (`minicpm_sala/`: a tiny manifest with
its own configuration, traffic and limit; program, reference, step work and
layer metrics are the benchmark's own files): the tiny cell through the
real runner on the CPU, the fp8 control, and the faults a compressed-key
cache, a selection and a state kept by row can have. Each planted fault
must read above the limit."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

import bench_rehearse as br
import test_benchmark_manifest as tm
from benchmark import harness

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "minicpm_sala")
CELL = "tiny-minicpm-longdoc"
REAL = "minicpm-sala-serve-longdoc"
SEED = 2 ** 31 + 29


def _rehearse(**kw):
    return br.rehearse(CELL, seconds=1.0, seed=SEED, root=ROOT, **kw)


def test_manifest_rules_hold_for_the_tiny_tree_and_the_real_cell():
    tm.test_names_units_and_keys(ROOT)
    tm.test_every_cell_finds_its_files(ROOT)
    tm.test_layer_metrics_agree_with_their_files(ROOT)
    tm.test_every_cell_finds_its_files(br.REPO)
    tm.test_layer_metrics_agree_with_their_files(br.REPO)
    tm.test_real_widths_are_the_published_ones(
        "minicpm-sala-serve-d8.json")
    real = harness.Cell.find(REAL)
    tiny = harness.Cell.find(CELL, root=ROOT, bench_dir=ROOT)
    # the rehearsal differs from the cell in sizes alone
    assert set(tiny.config) | {"published_as"} == set(real.config)
    assert set(tiny.config["assumed"]["sparse_config"]) \
        == set(real.config["assumed"]["sparse_config"])
    # every context of the real mix lies past dense_len, within max_seq
    mix, s = real.traffic, real.config["serving"]
    sparse = real.config["assumed"]["sparse_config"]
    assert mix["prompt_len"]["min"] > sparse["dense_len"]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        == s["max_blocks_per_seq"] * s["block_size"] \
        == real.config["assumed"]["max_context"]
    assert s["block_size"] == sparse["block_size"]
    assert s["num_blocks"] == s["max_batch"] * s["max_blocks_per_seq"] + 1
    # published layers 9-16, counted from 0
    assert real.config["mixer_types"] \
        == real.config["published"]["mixer_types"][9:17]


def test_the_manifest_only_grew():
    """What this configuration added to BENCHMARK.json: one configuration,
    one cell, three metrics of the kernels, and the cell's name at the end
    of the lists of the metrics it reports; not the experts' metrics."""
    m = harness.load_manifest()
    assert [c["name"] for c in m["configs"]][-1] == "minicpm-sala-serve-d8"
    assert [w["name"] for w in m["workloads"]][-1] == REAL
    assert [x["name"] for x in m["per_layer"]][-3:] == [
        "serve.sparse_walk_overhead", "serve.sparse_selected_page_share",
        "serve.sparse_attention_roofline"]
    for x in m["per_layer"]:
        if x["name"].startswith("serve.") and "sparse" not in x["name"]:
            moe = "moe" in x["name"] or "expert" in x["name"]
            assert (REAL in x["workloads"]) != moe, x["name"]
            assert x["workloads"][-1] == REAL or moe
    assert next(x for x in m["end_to_end"] if x["name"] == "tpot_p95_ms")[
        "workloads"][-1] == REAL


def test_real_configuration_is_counted_as_the_issue_reckoned():
    """The cut's arithmetic from the configuration's own keys: 2,820M
    parameters (5.64e9 B), the state a row keeps, the pages, and the step's
    work a token."""
    from paddle_tpu.models.minicpm_sala import MiniCPMSalaSpec

    cfg = harness.Cell.find(REAL).config
    s, sparse = cfg["serving"], cfg["assumed"]["sparse_config"]
    spec = MiniCPMSalaSpec.from_config(
        cfg, published_layers=cfg["published"]["num_hidden_layers"],
        **sparse)
    shapes = spec.param_shapes()
    n = sum(int(np.prod(sh)) for sh, _ in shapes.values())
    assert abs(n - 2.820e9) < 0.002e9
    per_layer = {k: sum(int(np.prod(sh)) for name, (sh, _) in shapes.items()
                        if name.startswith(f"layers.{i}."))
                 for k, i in (("sparse", 0), ("linear", 1))}
    assert abs(per_layer["sparse"] - 253.8e6) < 0.1e6
    assert abs(per_layer["linear"] - 285.2e6) < 0.1e6
    # a row's lightning state: 6 layers x [32, 128, 128] float32
    assert spec.count("lightning-attn") * 32 * 128 * 128 * 4 == 12_582_912
    # pages: 24 rows x 41,728 positions x 2 layers x 1,024 B, K and V
    pages = (s["num_blocks"] - 1) * s["block_size"] * 2 * 2 * 2 * 128 * 2
    assert abs(pages - 2.05e9) < 0.01e9
    work = harness.load_module("work", cfg["step_work"])
    token = work.flops(cfg, {"tokens": 1, "context": 0, "sampled": 0})
    # 2 x 2.218e9 matmul parameters of the layers + the recurrence; the
    # head's 2 x 0.30e9 for a token whose logits are sampled
    assert 4.44e9 < token < 4.46e9
    sampled = work.flops(cfg, {"tokens": 1, "context": 0, "sampled": 1})
    assert abs(sampled - token - 2 * 4096 * 73448) < 1
    far = work.flops(cfg, {"tokens": 1, "context": 30000, "sampled": 0})
    near = work.flops(cfg, {"tokens": 1, "context": 6208, "sampled": 0})
    assert far == near                     # the selection's keys, no more
    assert spec.selected_pages(30000) == 1 + 33 + 64
    assert spec.walked_slabs([(30000, 1), (20000, 400), (100, 1)]) \
        == 2 * (98 + 313 + 2)


def test_tiny_cell_through_the_real_runner(monkeypatch):
    br.stand_in_tracer(monkeypatch)
    cell, result, line = _rehearse(trace=True)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["compiles_in_window"] == 0
    m = line["metrics"]
    assert 0 < m["serve.step_mfu"]["value"] < 100
    # rows of one token read what they chose; a chunk's queries all pages
    assert m["serve.sparse_walk_overhead"]["value"] >= 1
    assert 0 < m["serve.sparse_selected_page_share"]["value"] < 100
    assert m["serve.rows_per_step"]["value"] > 1
    # no kernel event in a CPU trace: the roofline readers find nothing
    assert "serve.ssm_state_update_roofline" not in m
    assert "serve.sparse_attention_roofline" not in m


def test_control_fp8_fails():
    from benchmark import compare, weights
    from benchmark.runners import serve

    cell = harness.Cell.find(CELL, root=ROOT, bench_dir=ROOT)
    ref = harness.load_module("reference", cell.config["reference"])
    _, _, shapes = serve.build_engine(cell.config, 31)
    w = weights.make_like(shapes, cell.config, 31, donate=False)
    rng = np.random.default_rng(0)
    worst = 0.0
    for n in (60, 90, 40):
        tokens = rng.integers(1, 512, n).tolist()
        full = np.asarray(ref.logits_at(w, tokens, 0, cell.config))
        low = np.asarray(ref.logits_at(w, tokens, 0, cell.config,
                                       precision="fp8"))
        worst = max(worst, compare.served_gap(full, low.argmax(-1)))
    assert worst > cell.limits["served_logit_gap"]


# -- planted faults -------------------------------------------------------

def _state_not_carried(monkeypatch):
    """A row's lightning state lost between steps: every one-token row
    starts from zero."""
    from paddle_tpu.models import minicpm_sala as ms

    real = ms.ssm_state_update
    monkeypatch.setattr(
        ms, "ssm_state_update",
        lambda state, x, dt, a, b, c, d, slots, active, reset, **kw: real(
            state, x, dt, a, b, c, d, slots, active, jnp.ones_like(reset),
            **kw))


def _straddling_window_dropped(monkeypatch):
    """A compression window that straddles two steps is never written: the
    row's tail of keys is forgotten, so only windows wholly inside one
    step's tokens close (what a program that compresses each chunk alone
    computes)."""
    from paddle_tpu.models import minicpm_sala as ms

    real = ms._compress

    def compress(s, k, meta, ktail, ck, li):
        t = k.shape[0]
        inside = meta["off"] >= s.kernel_size - 1
        return real(s, k, dict(meta, real=meta["real"] & inside), ktail,
                    ck, li)

    monkeypatch.setattr(ms, "_compress", compress)


def _spec_fault(**over):
    """The selection computed under other sizes than the configuration's."""
    def plant(monkeypatch):
        import dataclasses

        from paddle_tpu.models import minicpm_sala as ms

        real = ms._select
        monkeypatch.setattr(
            ms, "_select", lambda s, *a: real(
                dataclasses.replace(s, **over), *a))
    return plant


def _first_block_left_out(monkeypatch):
    from paddle_tpu.models import minicpm_sala as ms

    real = ms._select

    def select(s, q, meta, ck, li):
        sel, counts = real(s, q, meta, ck, li)
        past = (meta["pos"] + 1 > s.dense_len)[:, None]
        mask = sel["page_mask"].at[:, :, 0].set(
            sel["page_mask"][:, :, 0] & ~past)
        # the lists' first entry is block 0: start each list at its second
        return dict(sel, page_mask=mask,
                    sel=jnp.roll(sel["sel"], -1, axis=-1),
                    n_sel=jnp.maximum(sel["n_sel"] - 1, 0)), counts

    monkeypatch.setattr(ms, "_select", select)


def _dense_past_dense_len(monkeypatch):
    _spec_fault(dense_len=10 ** 6)(monkeypatch)


def _rope_left_off(monkeypatch):
    from paddle_tpu.models import minicpm_sala as ms

    monkeypatch.setattr(ms, "_rope", lambda x, pos, theta: x)


def _decay_set_to_one(monkeypatch):
    from paddle_tpu.models import minicpm_sala as ms

    monkeypatch.setattr(ms.MiniCPMSalaSpec, "slopes", property(
        lambda self: (0.0,) * self.lightning_nh))


FAULTS = {
    "lightning-state-not-carried": _state_not_carried,
    "straddling-window-dropped": _straddling_window_dropped,
    "first-block-left-out": _first_block_left_out,
    "local-window-left-out": _spec_fault(window_size=4),
    "dense-past-dense_len": _dense_past_dense_len,
    "rope-left-off": _rope_left_off,
    "decay-set-to-one": _decay_set_to_one,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_reads_above_the_limit(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    _, _, line = _rehearse()
    gap = line["compared"]["served_logit_gap"]
    assert line["correct"] is False
    assert gap["value"] > gap["limit"]


def test_roofline_reader_prices_a_call_by_the_slabs_it_walked():
    """`serve.sparse_attention_roofline`: the kernel's events carry the
    whole page pool; the step span says how many slabs the step's walk had
    to read. Two traced steps of two calls, each call as long as its slabs'
    bytes take at the peak bandwidth, read 100."""
    from benchmark import reduce_trace as rt
    from benchmark.readers import kernel_roofline_rows
    from paddle_tpu.profiler import tracing

    st = "bf16[2,15649,2,64,128]{4,3,2,1,0}"
    name = ("%sparse_paged_attention.4 = bf16[2,8192,128]{2,1,0:T(8,128)"
            "(2,1)} custom-call(s32[16300]{0} %bt, s32[25]{0} %st, "
            "s32[26]{0} %cu, s32[64]{0} %first, s32[1]{0} %layer, "
            "s32[25]{0} %listed, s32[50]{0} %n_sel, s32[4900]{0} %sel, "
            "bf16[2,8192,128]{2,1,0} %q, s32[8192,1]{1,0} %lb, "
            "bf16[2,512,128]{2,1,0} %k, bf16[2,512,128]{2,1,0} %v, "
            f"f32[2,512,768]{{2,1,0}} %pm, {st} %kc, {st} %vc), "
            'custom_call_target="tpu_custom_call"')
    work = harness.load_module("work", "sparse_paged_attention").work
    peak = harness.load_json("peaks.json")["TPU v5 lite"]
    assert work("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)", {}) is None
    assert work(name.replace("%sparse_paged_attention", "%paged_attention"),
                {}) is None
    flops, nbytes = work(name, {}, rows=1000)
    assert nbytes == 1000 * 2 * 64 * 128 * 2           # K and V, bf16
    assert flops == 1000 * 64 * 16 * 4 * 128           # 16 heads a KV head
    assert work(name, {}) == (0.0, 0.0)
    ops, t = [], 0.0
    tracing.clear_ring()
    for slabs in (4704, 900):
        dur = work(name, {}, rows=slabs)[1] / peak["hbm_bytes_per_s"]
        tracing.record_span("serving::step", t, t + 2 * dur, traced=True,
                            args={"rows": 25, "sparse_pages_walked": slabs})
        for _ in range(2):
            ops.append((name, t, t + dur))
            t += dur
    trace = rt.Trace(device_ops={0: ops}, annotations=[])
    ctx = harness.ReadContext(trace, (0.0, t), {}, {}, peak, 1, {})
    spec = harness.load_json("layer_metrics",
                             "serve.sparse_attention_roofline.json")
    got = kernel_roofline_rows.read(spec["params"], ctx)
    assert got == pytest.approx(100.0, rel=1e-6)
    # a program whose span lacks the argument (the parent): nothing, no error
    tracing.clear_ring()
    tracing.record_span("serving::step", 0.0, 1.0, traced=True,
                        args={"rows": 3})
    assert kernel_roofline_rows.read(spec["params"], ctx) is None
    tracing.clear_ring()


def test_the_parent_refuses_the_new_cell_at_once(monkeypatch):
    """On a checkout from before the model, the program file says so as a
    BenchmarkError (exit 1 and a message, no traceback, no hang)."""
    import builtins

    program = harness.load_module("programs", "minicpm_sala")
    real = builtins.__import__

    def no_model(name, *a, **kw):
        if name.endswith("minicpm_sala") and "paddle_tpu" in name:
            raise ImportError(f"No module named {name!r}")
        return real(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", no_model)
    with pytest.raises(harness.BenchmarkError, match="minicpm_sala.py"):
        program.build_engine(harness.Cell.find(REAL).config, 1)
