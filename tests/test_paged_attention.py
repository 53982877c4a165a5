"""The paged-attention Pallas kernel (ops/pallas/paged_attention.py, ISSUE
26) in interpret mode on the CPU: against the gathered reference it
replaces, case by case, and under the serving engine — greedy streams of a
float32 engine with the kernel equal those of the reference path token for
token, through step(), decode_run() and an armed n-gram drafter, and
`serving/paged_kernel_steps` says how often the kernel engaged."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (PagedCausalLM,
                                          PagedServingConfig, ServingEngine)
from paddle_tpu.inference.speculative import NGramDrafter
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.profiler import metrics as _metrics

D, BS = 128, 32


def _counters():
    return dict(_metrics.snapshot()["counters"])


def _delta(c0, name):
    return _counters().get(name, 0) - c0.get(name, 0)


# each case: (this, start) per row, the LAST row being the engine's padding
# row (block table all page 0, start 0); hq/hkv; max_blocks; what it tries
CASES = {
    "decode_rows_only": dict(this=[1, 1, 1, 0], start=[37, 63, 5, 0]),
    "chunk_and_decode_mixed": dict(this=[1, 20, 1, 10],
                                   start=[40, 0, 95, 0]),
    "chunk_starts_mid_page": dict(this=[24, 1, 0], start=[45, 12, 0]),
    "chunk_ends_on_page_edge": dict(this=[19, 1, 0], start=[45, 31, 0]),
    "row_at_max_seq": dict(this=[1, 30, 0], start=[127, 98, 0],
                           max_blocks=4),
    "row_with_zero_tokens": dict(this=[1, 0, 17, 0, 0],
                                 start=[70, 50, 33, 90, 0]),
    "pad_row_inside_a_page": dict(this=[1, 9, 22], start=[64, 10, 0]),
    "pad_row_past_a_page": dict(this=[1, 9, 118], start=[64, 10, 0]),
    "long_chunk_over_query_blocks": dict(this=[150, 1, 1, 40],
                                         start=[64, 200, 31, 0],
                                         max_blocks=8),
    "mha": dict(this=[1, 21, 1, 9], start=[33, 40, 64, 0], hq=2, hkv=2),
    "one_kv_head": dict(this=[1, 21, 1, 9], start=[33, 40, 64, 0], hq=4,
                        hkv=1),
    "pages_in_order": dict(this=[1, 20, 1, 10], start=[40, 0, 95, 0],
                           shuffle=False),
}


def _build(case, dtype, seed=0, layers=2, layer=1):
    """Random caches and a random step; returns the kernel's arguments
    and the caches with the step's tokens written, for the reference."""
    this, start = case["this"], np.asarray(case["start"], np.int32)
    hq, hkv = case.get("hq", 8), case.get("hkv", 2)
    rows, t = len(this), int(sum(this))
    mb = case.get("max_blocks", 6)
    need = [-(-(int(s) + n) // BS) for s, n in zip(start, this)]
    nb = 1 + sum(need[:-1])
    rng = np.random.default_rng(seed)
    kc = jnp.asarray(rng.normal(size=(layers, nb, hkv, BS, D)), dtype)
    vc = jnp.asarray(rng.normal(size=(layers, nb, hkv, BS, D)), dtype)
    q = jnp.asarray(rng.normal(size=(t, hq, D)), dtype)
    k = jnp.asarray(rng.normal(size=(t, hkv, D)), dtype)
    v = jnp.asarray(rng.normal(size=(t, hkv, D)), dtype)
    # pages deliberately out of order, none shared, page 0 the trash page
    free = np.arange(1, nb)
    if case.get("shuffle", True):
        free = rng.permutation(free)
    bt = np.zeros((rows, mb), np.int32)
    at = 0
    for b in range(rows - 1):
        bt[b, :need[b]] = free[at:at + need[b]]
        at += need[b]
    cu = np.zeros(rows + 1, np.int32)
    cu[1:] = np.cumsum(this)
    t2b = np.searchsorted(cu[1:], np.arange(t), side="right")
    pos = start[t2b] + np.arange(t) - cu[t2b]
    page, slot = bt[t2b, pos // BS], pos % BS
    kc_w = kc.at[layer, page, :, slot].set(k)
    vc_w = vc.at[layer, page, :, slot].set(v)
    meta = (jnp.asarray(bt), jnp.asarray(start), jnp.asarray(cu))
    return (q, k, v, kc, vc) + meta, (q, kc_w, vc_w) + meta, t2b < rows - 1


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_matches_gathered_reference(monkeypatch, name, dtype, tol):
    """Float32 to 1e-5, bf16 to 2e-2 of the largest entry (chip_smoke's
    tolerance), on every token of every real row. The padding row's tokens
    are compared only while they fit one page: past it they overwrite each
    other in page 0, the reference reads that back, and the kernel reads
    the pack — neither is an answer anyone uses; they must be finite."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    args, ref_args, real = _build(CASES[name], dtype)
    assert pa.use_kernel(args[0], args[3])
    out = jax.jit(lambda *a: pa.paged_attention(*a, layer_idx=1))(*args)
    ref = pa.paged_attention_ref(*ref_args, layer_idx=1)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(out).all()
    if CASES[name]["this"][-1] <= BS:
        real = np.ones_like(real)
    assert real.any()
    scale = np.abs(ref).max() if dtype == jnp.bfloat16 else 1.0
    assert np.abs(out - ref)[real].max() <= tol * scale


def test_unstacked_cache_and_a_token_no_row_owns(monkeypatch):
    """`layer_idx=None` takes `[num_blocks, HKV, block, D]` caches; a token
    past `cu_seqlens_q[-1]` belongs to no row and reads 0."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    args, ref_args, _ = _build(CASES["chunk_and_decode_mixed"],
                               jnp.float32, layers=1, layer=0)
    q, k, v, kc, vc, bt, start, cu = args
    out = pa.paged_attention(q, k, v, kc[0], vc[0], bt, start, cu)
    ref = pa.paged_attention_ref(ref_args[0], ref_args[1][0],
                                 ref_args[2][0], bt, start, cu)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    short = cu.at[-1].set(cu[-2])          # the last row's tokens: nobody's
    out = np.asarray(pa.paged_attention(q, k, v, kc[0], vc[0], bt, start,
                                        short))
    assert np.abs(out[int(cu[-2]):]).max() == 0.0
    np.testing.assert_allclose(out[:int(cu[-2])],
                               np.asarray(ref)[:int(cu[-2])], atol=1e-5)


@pytest.mark.parametrize("why,dtype,cache_dtype,d,bs,quant", [
    ("kernels_off", jnp.float32, jnp.float32, 128, 32, False),
    ("int8_cache", jnp.bfloat16, jnp.int8, 128, 32, True),
    ("head_dim_64", jnp.float32, jnp.float32, 64, 32, False),
    ("block_8_bf16", jnp.bfloat16, jnp.bfloat16, 128, 8, False),
])
def test_where_the_reference_runs(monkeypatch, why, dtype, cache_dtype, d,
                                  bs, quant):
    """The choice rests on what the code sees (backend, cache dtype,
    shapes); with kernels on, a fall to the reference is counted."""
    if why != "kernels_off":
        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    q = jax.ShapeDtypeStruct((16, 4, d), dtype)
    cache = jax.ShapeDtypeStruct((2, 9, 2, bs, d), cache_dtype)
    c0 = _counters()
    assert not pa.use_kernel(q, cache, quant)
    assert _delta(c0, "pallas/reference_dispatch/paged_attention") \
        == (0 if why == "kernels_off" else 1)


# ---------------------------------------------------------------------------
# under the engine
# ---------------------------------------------------------------------------

ENGINE = dict(vocab_size=97, hidden_size=256, num_layers=2, num_heads=2,
              num_kv_heads=1, ffn_size=256, block_size=BS, num_blocks=40,
              max_batch=4, max_blocks_per_seq=6, token_budget=128,
              dtype="float32")
PROMPTS = [(160, 6), (5, 7), (33, 6)]      # (prompt tokens, new tokens)
LATE = (20, 5)


def _engine(**over):
    """A model of its own each time: an engine's step programs are shared
    through the model, and are traced with or without the kernel."""
    cfg = PagedServingConfig(**{**ENGINE, **over})
    paddle.seed(3)
    model = PagedCausalLM(cfg)
    model.eval()
    return ServingEngine.from_model(model, cfg, seed=0), cfg


def _prompts():
    rng = np.random.default_rng(26)
    return [(rng.integers(1, 97, n).tolist(), new)
            for n, new in PROMPTS + [LATE]]


def _serve(eng, drafter=None, window=0):
    """Three requests at once (one longer than the token budget: chunked
    prefill beside decode rows), a fourth joining mid-flight. Returns the
    streams and how many steps ran the fresh-prefill program (every row at
    cache position 0: the varlen branch, not this kernel's)."""
    if drafter is not None:
        eng.set_drafter(drafter, k=4)
    prompts = _prompts()
    c0 = _counters()
    rids = [eng.add_request(p, max_new_tokens=n) for p, n in prompts[:3]]
    eng.step()                              # all rows fresh: 1 such step
    eng.step()
    rids.append(eng.add_request(prompts[3][0],
                                max_new_tokens=prompts[3][1]))
    if window:
        while any(r.length - r.cached != 1 for r in eng.pending()):
            eng.step()
        eng.decode_run(window)
    out = eng.run_to_completion()
    return [out[r] for r in rids], \
        _delta(c0, "serving/steps"), _delta(c0, "serving/paged_kernel_steps")


@pytest.fixture(scope="module")
def reference_streams():
    eng, _ = _engine()
    c0 = _counters()
    streams, steps, paged = _serve(eng)
    assert steps > 4 and paged == 0         # kernels off: never counted
    assert _delta(c0, "pallas/reference_dispatch/paged_attention") == 0
    return streams


@pytest.mark.parametrize("path", ["step", "decode_run", "ngram_drafter"])
def test_engine_streams_equal_the_reference_path(monkeypatch,
                                                 reference_streams, path):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    eng, cfg = _engine()
    drafter = None
    if path == "ngram_drafter":
        drafter = NGramDrafter(block_size=cfg.block_size)
        for (p, _), s in zip(_prompts(), reference_streams):
            drafter.observe(list(p) + list(s))
    c0 = _counters()
    streams, steps, paged = _serve(
        eng, drafter, window=4 if path == "decode_run" else 0)
    assert streams == reference_streams
    # every step but the one fresh prefill ran a program with the kernel
    assert paged == steps - 1 and paged > 0
    assert _delta(c0, "pallas/reference_dispatch/paged_attention") == 0
    if path == "ngram_drafter":
        assert _delta(c0, "serving/spec_steps") > 0
        assert eng._kernel_programs["serving_spec_verify"]["paged_attention"]
    assert {k: v["paged_attention"]
            for k, v in eng._kernel_programs.items()} == {
        "serving_step": True, "serving_fresh_prefill": False,
        **({"serving_spec_verify": True} if drafter else {})}


def test_int8_cache_engine_takes_the_reference_and_is_counted(monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    eng, _ = _engine(cache_quant="int8")
    c0 = _counters()
    streams, steps, paged = _serve(eng)
    assert [len(s) for s in streams] == [n for _, n in PROMPTS + [LATE]]
    assert paged == 0 and steps > 4
    # counted where a step program is traced: once a layer, in the mixed
    # step and in the fresh-prefill step (whose page write asks too)
    assert _delta(c0, "pallas/reference_dispatch/paged_attention") \
        == 2 * ENGINE["num_layers"]


def test_exported_step_holds_the_reference(tmp_path, monkeypatch):
    """`save_paged_model` exports off the chip: kernels off, so the
    artifact holds the gathered formulation and its engine counts 0."""
    from paddle_tpu.inference.serving import save_paged_model

    cfg = PagedServingConfig(**ENGINE)
    paddle.seed(3)
    model = PagedCausalLM(cfg)
    model.eval()
    prefix = str(tmp_path / "paged")
    save_paged_model(prefix, model)
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    eng = ServingEngine(prefix, cfg)
    c0 = _counters()
    eng.add_request(_prompts()[1][0], max_new_tokens=3)
    out = eng.run_to_completion()
    assert len(next(iter(out.values()))) == 3
    assert _delta(c0, "serving/steps") == 3
    assert _delta(c0, "serving/paged_kernel_steps") == 0
