"""The page-write Pallas kernel (ops/pallas/kv_page_write.py, ISSUE 30) in
interpret mode on the CPU: against the scatter it replaces, bit for bit,
case by case; and under the serving engine, whose step programs take the
page stacks donated — streams equal the scatter path's token for token
through step(), decode_run(), an armed n-gram drafter and a prefix-cache
hit, `serving/kv_inplace_steps` says how often the kernel engaged, and
every caller keeps what a step returns."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import functional as IF
from paddle_tpu.inference.serving import (PagedCausalLM,
                                          PagedServingConfig, ServingEngine)
from paddle_tpu.inference.speculative import NGramDrafter
from paddle_tpu.ops.pallas import kv_page_write as kw
from paddle_tpu.profiler import metrics as _metrics

D, BS = 128, 32


def _counters():
    return dict(_metrics.snapshot()["counters"])


def _delta(c0, name):
    return _counters().get(name, 0) - c0.get(name, 0)


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


# each case: (this, start) per row, the LAST row being the engine's padding
# row (block table all page 0, start 0)
CASES = {
    "decode_rows_only": dict(this=[1, 1, 1, 0], start=[37, 63, 5, 0]),
    "chunk_starts_mid_page_spans_three": dict(this=[60, 1, 0],
                                              start=[20, 12, 0]),
    "chunk_ends_on_page_edge": dict(this=[19, 1, 0], start=[45, 31, 0]),
    "rows_with_no_tokens": dict(this=[1, 0, 17, 0, 0],
                                start=[70, 50, 33, 90, 0]),
    "padding_row_holds_most": dict(this=[1, 9, 118], start=[64, 10, 0]),
}


def _build(case, dtype, stacked, seed=0, hkv=2, layers=2):
    """Random caches and a random step: (kc, vc, k, v, bt, start, cu)."""
    this, start = case["this"], np.asarray(case["start"], np.int32)
    rows, t = len(this), int(sum(this))
    max_blocks = 4
    rng = np.random.default_rng(seed)
    n_pages = 1 + (rows - 1) * max_blocks
    bt = np.zeros((rows, max_blocks), np.int32)
    bt[:rows - 1] = (1 + rng.permutation(n_pages - 1)) \
        .reshape(rows - 1, max_blocks)
    shape = ((layers,) if stacked else ()) + (n_pages, hkv, BS, D)

    def draw(shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    cu = np.concatenate([[0], np.cumsum(this)]).astype(np.int32)
    return (draw(shape), draw(shape), draw((t, hkv, D)), draw((t, hkv, D)),
            jnp.asarray(bt), jnp.asarray(start), jnp.asarray(cu))


@pytest.mark.parametrize("stacked", [True, False],
                         ids=["stacked", "one_pool"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_equals_the_scatter_bit_for_bit(monkeypatch, name, dtype,
                                               stacked):
    """Every page but the trash page holds the scatter's bits (the padding
    row's tokens, which the scatter drops into page 0, are skipped), and
    the trash page is left as it was."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    args = _build(CASES[name], dtype, stacked)
    layer = dict(layer_idx=1) if stacked else {}
    assert kw.use_kernel(args[2], args[0])
    got = kw.kv_page_write(*args, **layer, last_row_is_padding=True)
    ref = kw.kv_page_write_ref(*args, **layer)
    live = (slice(None), slice(1, None)) if stacked else slice(1, None)
    trash = (slice(None), 0) if stacked else 0
    for g, r, before in zip(got, ref, args[:2]):
        assert g.dtype == before.dtype and g.shape == before.shape
        assert np.array_equal(_bits(g)[live], _bits(r)[live])
        assert np.array_equal(_bits(g)[trash], _bits(before)[trash])
    if stacked:                  # the other layer: untouched
        assert np.array_equal(_bits(got[0])[0], _bits(args[0])[0])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_every_row_is_written_unless_the_last_is_padding(monkeypatch, dtype):
    """The public function's rows are all real unless the caller says
    otherwise: without `last_row_is_padding` the last row's tokens reach
    their pages too, and the whole result is the scatter's."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    kc, vc, k, v, bt, start, cu = _build(
        dict(this=[1, 9, 40, 0], start=[64, 10, 7, 0]), dtype, True)
    bt, start, cu = bt[:3], start[:3], cu[:4]      # no padding row
    got = kw.kv_page_write(kc, vc, k, v, bt, start, cu, layer_idx=0)
    ref = kw.kv_page_write_ref(kc, vc, k, v, bt, start, cu, layer_idx=0)
    for g, r in zip(got, ref):
        assert np.array_equal(_bits(g), _bits(r))


def test_two_layers_written_in_turn(monkeypatch):
    """One jitted call of the kernel serves every layer (the layer is a
    run-time scalar): layer 0 then layer 1 of one stack, each with its own
    keys, equal the two scatters; tracing the second adds no trace."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    kc, vc, k, v, bt, start, cu = _build(
        CASES["chunk_starts_mid_page_spans_three"], jnp.bfloat16, True)
    rest = (bt, start, cu)
    g = kw.kv_page_write(kc, vc, k, v, *rest, layer_idx=0,
                         last_row_is_padding=True)
    traces = kw._write_call._cache_size()
    g = kw.kv_page_write(*g, v, k, *rest, layer_idx=1,
                         last_row_is_padding=True)
    assert kw._write_call._cache_size() == traces
    r = kw.kv_page_write_ref(kc, vc, k, v, *rest, layer_idx=0)
    r = kw.kv_page_write_ref(*r, v, k, *rest, layer_idx=1)
    for a, b in zip(g, r):
        assert np.array_equal(_bits(a)[:, 1:], _bits(b)[:, 1:])


@pytest.mark.parametrize("why,dtype,cache_dtype,d,bs,quant", [
    ("kernels_off", jnp.float32, jnp.float32, 128, 32, False),
    ("int8_cache", jnp.bfloat16, jnp.int8, 128, 32, True),
    ("head_dim_64", jnp.float32, jnp.float32, 64, 32, False),
    ("block_8_bf16", jnp.bfloat16, jnp.bfloat16, 128, 8, False),
])
def test_where_the_scatter_stays(monkeypatch, why, dtype, cache_dtype, d, bs,
                                 quant):
    """The predicate is the paged kernel's, counted under its name: no
    second dispatch count."""
    if why != "kernels_off":
        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    k = jax.ShapeDtypeStruct((16, 2, d), dtype)
    cache = jax.ShapeDtypeStruct((2, 9, 2, bs, d), cache_dtype)
    c0 = _counters()
    assert not kw.use_kernel(k, cache, quant)
    assert _delta(c0, "pallas/reference_dispatch/paged_attention") \
        == (0 if why == "kernels_off" else 1)
    assert _delta(c0, "pallas/reference_dispatch/kv_page_write") == 0


@pytest.mark.parametrize("mode", ["mixed", "fresh_prefill"])
def test_block_multihead_attention_writes_through_the_kernel(monkeypatch,
                                                             mode):
    """The public function, one pool (no `layer_idx`), every row real: the
    returned caches equal the scatter path's bit for bit, in the mixed and
    in the fresh-prefill mode (whose last row is padding by contract)."""
    rng = np.random.default_rng(5)
    fresh = mode == "fresh_prefill"
    this = [5, 40, 7] if fresh else [1, 40, 1]
    dec = [0, 0, 0] if fresh else [33, 20, 64]
    enc = this if fresh else [0, 0, 0]
    bt = np.zeros((3, 4), np.int32)
    bt[:2 if fresh else 3] = 1 + rng.permutation(12)[:8 if fresh else 12] \
        .reshape(-1, 4)
    t = sum(this)
    qkv = rng.standard_normal((t, (4 + 2 * 2) * D)).astype(np.float32)
    pool = rng.standard_normal((13, 2, BS, D)).astype(np.float32)
    cu = np.concatenate([[0], np.cumsum(this)]).astype(np.int32)

    def run():
        col = [paddle.to_tensor(np.asarray(x, np.int32).reshape(-1, 1))
               for x in (enc, dec, this)]
        out = IF.block_multihead_attention(
            paddle.to_tensor(qkv), paddle.to_tensor(pool),
            paddle.to_tensor(pool[::-1].copy()), *col, None, None,
            paddle.to_tensor(cu), None, paddle.to_tensor(bt),
            block_size=BS, fresh_prefill=fresh)
        return [np.asarray(x.numpy()) for x in (out[0], out[2], out[3])]

    ref = run()
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    calls = kw.traced_kernel_calls()
    got = run()
    assert kw.traced_kernel_calls() == calls + 1
    live = slice(1, None) if fresh else slice(None)
    for g, r in zip(got[1:], ref[1:]):
        assert np.array_equal(_bits(g)[live], _bits(r)[live])
    rows = slice(0, cu[2]) if fresh else slice(None)
    np.testing.assert_allclose(got[0][rows], ref[0][rows], atol=2e-5)


# ---------------------------------------------------------------------------
# under the engine
# ---------------------------------------------------------------------------

ENGINE = dict(vocab_size=97, hidden_size=256, num_layers=2, num_heads=2,
              num_kv_heads=1, ffn_size=256, block_size=BS, num_blocks=40,
              max_batch=4, max_blocks_per_seq=6, token_budget=128,
              dtype="float32")
PROMPTS = [(160, 6), (5, 7), (33, 6)]      # (prompt tokens, new tokens)
LATE = (20, 5)
PATHS = ["step", "decode_run", "ngram_drafter", "prefix_cache"]


def _model(**over):
    """A model of its own each time: an engine's step programs are shared
    through the model, and are traced with or without the kernels."""
    cfg = PagedServingConfig(**{**ENGINE, **over})
    paddle.seed(3)
    model = PagedCausalLM(cfg)
    model.eval()
    return model, cfg


def _engine(**over):
    model, cfg = _model(**over)
    return ServingEngine.from_model(model, cfg, seed=0), cfg


def _prompts(shared=0):
    """`shared`: how many leading tokens every prompt has in common."""
    rng = np.random.default_rng(26)
    head = rng.integers(1, 97, shared).tolist()
    return [((head + rng.integers(1, 97, n).tolist())[:max(n, shared + 1)],
             new) for n, new in PROMPTS + [LATE]]


def _serve(path, streams=None):
    """Three requests at once (one longer than the token budget: chunked
    prefill beside decode rows), a fourth joining mid-flight. Returns the
    streams and the counts of steps, of steps with the page-write kernel
    and of prefix-cache pages reused."""
    prefix = path == "prefix_cache"
    eng, cfg = _engine(prefix_cache=prefix)
    prompts = _prompts(shared=2 * BS if prefix else 0)
    if path == "ngram_drafter":
        drafter = NGramDrafter(block_size=cfg.block_size)
        for (p, _), s in zip(prompts, streams):
            drafter.observe(list(p) + list(s))
        eng.set_drafter(drafter, k=4)
    c0 = _counters()
    rids = [eng.add_request(p, max_new_tokens=n) for p, n in prompts[:3]]
    eng.step()
    eng.step()
    rids.append(eng.add_request(*prompts[3][:1],
                                max_new_tokens=prompts[3][1]))
    if path == "decode_run":
        while any(r.length - r.cached != 1 for r in eng.pending()):
            eng.step()
        eng.decode_run(4)
    out = eng.run_to_completion()
    return [out[r] for r in rids], _delta(c0, "serving/steps"), \
        _delta(c0, "serving/kv_inplace_steps"), \
        _delta(c0, "serving/prefix_pages_reused"), \
        _delta(c0, "serving/spec_steps")


@pytest.fixture(scope="module")
def scatter_streams():
    """Kernels off: the scatter, the gathered attention, 0 counted."""
    out = {}
    for path in ("step", "prefix_cache"):
        streams, steps, inplace, reused, _ = _serve(path)
        assert steps > 4 and inplace == 0
        assert (reused > 0) == (path == "prefix_cache")
        out[path] = streams
    return out


@pytest.mark.parametrize("path", PATHS)
def test_engine_streams_equal_the_scatter_path(monkeypatch, scatter_streams,
                                               path):
    want = scatter_streams["prefix_cache" if path == "prefix_cache"
                           else "step"]
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    c0 = _counters()
    streams, steps, inplace, reused, spec = _serve(path, want)
    assert streams == want
    # every step's program holds the page-write kernel: the fresh-prefill
    # step writes pages too
    assert inplace == steps > 3
    assert _delta(c0, "pallas/reference_dispatch/paged_attention") == 0
    assert (reused > 0) == (path == "prefix_cache")
    assert (spec > 0) == (path == "ngram_drafter")


def test_int8_cache_engine_keeps_the_scatter(monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    eng, _ = _engine(cache_quant="int8")
    c0 = _counters()
    eng.add_request(_prompts()[1][0], max_new_tokens=4)
    kc, ks = eng._kc, eng._ks
    out = eng.run_to_completion()
    assert len(next(iter(out.values()))) == 4
    assert _delta(c0, "serving/steps") == 4
    assert _delta(c0, "serving/kv_inplace_steps") == 0
    assert all(not held["kv_page_write"]
               for held in eng._kernel_programs.values())
    # its pages and its scale pools are donated all the same
    assert kc.is_deleted() and ks.is_deleted()
    assert not eng._kc.is_deleted() and not eng._ks.is_deleted()


@pytest.mark.parametrize("kernels", [False, True], ids=["scatter", "kernel"])
def test_steps_take_the_stacks_donated_and_keep_what_returns(monkeypatch,
                                                             kernels):
    """After a step of each kind (fresh prefill, mixed, a decode window, a
    verify step) the arrays the engine held before it are deleted and the
    engine's own handles are live."""
    if kernels:
        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    eng, cfg = _engine()
    prompts = _prompts()
    seen = []

    def after(what, run):
        kc, vc = eng._kc, eng._vc
        run()
        assert kc.is_deleted() and vc.is_deleted(), what
        assert not eng._kc.is_deleted() and not eng._vc.is_deleted(), what
        seen.append(what)

    eng.add_request(prompts[0][0], max_new_tokens=12)
    eng.add_request(prompts[1][0], max_new_tokens=12)
    after("fresh_prefill", eng.step)
    after("mixed", eng.step)
    while any(r.length - r.cached != 1 for r in eng.pending()):
        eng.step()
    after("decode_window", lambda: eng.decode_run(2))
    drafter = NGramDrafter(block_size=cfg.block_size)
    for r in eng.pending():
        drafter.observe(r.prompt + r.generated)
    eng.set_drafter(drafter, k=2)
    c0 = _counters()
    after("verify", eng.step)
    assert _delta(c0, "serving/spec_steps") == 1
    out = eng.run_to_completion()
    assert [len(s) for s in out.values()] == [12, 12]
    assert set(eng._kernel_programs) == {
        "serving_step", "serving_fresh_prefill", "serving_spec_verify"}
    assert all(held["kv_page_write"] == kernels
               for held in eng._kernel_programs.values())


@pytest.mark.parametrize("kernels", [False, True], ids=["scatter", "kernel"])
def test_probe_logits_then_step(monkeypatch, kernels):
    """The probe runs a step program over the engine's donated stacks and
    keeps what it returns: a step after it finds live stacks, and the
    stream is the one an unprobed engine gives."""
    if kernels:
        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    prompt = _prompts()[1][0]
    eng, _ = _engine()
    eng.add_request(prompt, max_new_tokens=5)
    want = next(iter(eng.run_to_completion().values()))

    eng, cfg = _engine()
    kc = eng._kc
    logits = eng.probe_logits(prompt)
    assert logits.shape == (cfg.vocab_size,) and np.isfinite(logits).all()
    assert kc.is_deleted() and not eng._kc.is_deleted()
    assert int(np.argmax(logits)) == want[0]
    eng.add_request(prompt, max_new_tokens=5)
    eng.step()
    logits2 = eng.probe_logits(prompt)           # between steps too
    np.testing.assert_allclose(logits2, logits, atol=1e-4)
    assert next(iter(eng.run_to_completion().values())) == want


@pytest.mark.parametrize("kernels", [False, True], ids=["scatter", "kernel"])
def test_two_engines_of_one_model_step_in_turn(monkeypatch, kernels):
    """`_serving_shared`: two engines share the compiled step programs and
    each donates its own stacks; their streams equal a lone engine's."""
    if kernels:
        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    model, cfg = _model()
    a = ServingEngine.from_model(model, cfg, seed=0)
    b = ServingEngine.from_model(model, cfg, seed=0)
    assert a._compiled is b._compiled and a._kc is not b._kc
    prompts = _prompts()
    ra = a.add_request(prompts[0][0], max_new_tokens=5)
    rb = b.add_request(prompts[2][0], max_new_tokens=5)
    c0 = _counters()
    while a.pending() or b.pending():
        for eng in (a, b):
            if eng.pending():
                eng.step()
    steps = _delta(c0, "serving/steps")
    assert _delta(c0, "serving/kv_inplace_steps") == (steps if kernels else 0)
    lone = ServingEngine.from_model(model, cfg, seed=0)
    rl = [lone.add_request(prompts[i][0], max_new_tokens=5) for i in (0, 2)]
    out = lone.run_to_completion()
    assert a._requests[ra].generated == out[rl[0]]
    assert b._requests[rb].generated == out[rl[1]]
