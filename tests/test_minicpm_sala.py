"""MiniCPM-SALA through `ServingEngine` at a toy size whose sparse sizes make
every branch run in a few hundred tokens (kernel 4, stride 2, block 8,
top-k 2, window 16, dense_len 32; hidden 64, float32 weights), against the
benchmark's plain reference by LOGITS: chunked prefill whose chunks end
inside a compression window and inside a block, rows that cross `dense_len`,
pre-emption, two rows with different selections, the kernels in interpret
mode, and the counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference import serving as sv
from paddle_tpu.inference.serving import PagedServingConfig, ServingEngine
from paddle_tpu.models import minicpm_sala as ms
from paddle_tpu.models.minicpm_sala import (MiniCPMSala, MiniCPMSalaSpec,
                                            init_params)

SPARSE = dict(kernel_size=4, kernel_stride=2, block_size=8, topk=2,
              init_blocks=1, window_size=16, dense_len=32)
SIZES = dict(
    vocab_size=128, hidden_size=64, intermediate_size=128,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                 "minicpm4"],
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
    rms_norm_eps=1e-6, rope_theta=10000, scale_emb=12, scale_depth=1.4,
    dim_model_base=32, num_hidden_layers=4,
    assumed={"sparse_config": SPARSE})
# float32 through one recurrence and one softmax a layer: 2e-6 in these
# runs; the fp8 control reads 1e-2 and more (test_benchmark_minicpm_sala)
TOL = 2e-5


def make_model(seed=3, **over):
    spec = MiniCPMSalaSpec.from_config(
        dict(SIZES, **over), published_layers=4, chunk_size=8,
        dtype="float32", **SPARSE)
    return MiniCPMSala(spec, init_params(spec, seed=seed, std=0.2))


def make_engine(model=None, **over):
    kw = dict(vocab_size=128, hidden_size=64, num_layers=4, num_heads=4,
              num_kv_heads=2, ffn_size=128, block_size=8, num_blocks=65,
              max_batch=3, max_blocks_per_seq=16, token_budget=13,
              dtype="float32")
    kw.update(over)
    return ServingEngine.from_model(model or make_model(),
                                    PagedServingConfig(**kw), seed=1)


def reference_logits(model, tokens, first):
    from benchmark.reference import minicpm_sala_serve as ref

    return np.asarray(ref.logits_at(model.params, tokens, first, SIZES))


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, n).tolist() for n in lengths]


def counters():
    from paddle_tpu.profiler import metrics

    return dict(metrics.snapshot()["counters"])


def serve_with_logits(eng, ps, max_new, monkeypatch):
    """(tokens, logits) a request: every sampled step's logits row."""
    seen = []
    real = sv.ServingEngine._sample_dev

    def spy(logits, *a):
        seen.append(np.asarray(logits, np.float32))
        return real(logits, *a)

    monkeypatch.setattr(sv.ServingEngine, "_sample_dev", staticmethod(spy))
    rids = [eng.add_request(p, max_new_tokens=max_new) for p in ps]
    out = eng.run_to_completion()
    return [out[r] for r in rids], seen


@pytest.mark.parametrize("length,new", [(28, 14), (50, 8), (75, 20)],
                         ids=["crosses-dense_len-decoding",
                              "crosses-dense_len-in-a-chunk", "long"])
def test_chunked_prefill_then_decode_agree_with_the_reference(length, new,
                                                              monkeypatch):
    """Chunks of 13 (ends inside a window of 4 and a block of 8), then rows
    of one token: every sampled step's LOGITS are the plain reference's
    full forward's, across dense_len, window and block boundaries."""
    model = make_model()
    eng = make_engine(model)
    (p,) = prompts(length, seed=length)
    (toks,), seen = serve_with_logits(eng, [p], new, monkeypatch)
    lg = reference_logits(model, p + toks, len(p) - 1)[:new]
    assert len(seen) == new
    for i, row in enumerate(seen):
        np.testing.assert_allclose(row[0], lg[i], atol=TOL)
    assert toks == lg.argmax(-1).tolist()
    assert len(eng._free_slots) == 3 and not eng.pending()


def test_two_rows_with_different_selections_in_one_step():
    """Three requests decode together past dense_len, each with its own
    selection; each serves the reference's tokens."""
    from benchmark import compare

    model = make_model()
    eng = make_engine(model)
    ps = prompts(60, 45, 70, seed=9)
    rids = [eng.add_request(p, max_new_tokens=16) for p in ps]
    before = counters()
    out = eng.run_to_completion()
    for rid, p in zip(rids, ps):
        lg = reference_logits(model, p + out[rid], len(p) - 1)[:16]
        assert out[rid] == lg.argmax(-1).tolist()
        assert compare.served_gap(lg, out[rid]) < TOL
    c = counters()
    assert c["serving/sparse_pages_selected"] \
        > before.get("serving/sparse_pages_selected", 0)
    assert c["serving/ssm_rows_decode"] > before.get(
        "serving/ssm_rows_decode", 0)


def test_selection_against_brute_force_and_rows_differ():
    """`_select` for two rows of one token with different compressed keys:
    each (row, KV head) list is block 0, the local blocks and the two best
    others by a numpy brute force; the two rows choose differently; the
    mask's rows hold the lists."""
    model = make_model()
    s = model.spec
    hkv, g, d, per = 2, 2, 16, 4
    mb, b1, t = 16, 3, 4
    rng = np.random.default_rng(4)
    ck = jnp.asarray(rng.normal(size=(2, 40, per * hkv * d)), jnp.float32)
    bt = jnp.asarray(np.stack([np.arange(1, 17), np.arange(17, 33),
                               np.zeros(16)]), jnp.int32)
    q = jnp.asarray(rng.normal(size=(t, hkv * g, d)), jnp.float32)
    start = jnp.asarray([90, 67, 0], jnp.int32)
    this = jnp.asarray([1, 1, 2], jnp.int32)
    cu = jnp.asarray([0, 1, 2, 4], jnp.int32)
    meta = ms.rows_of(t, jnp.zeros(3, jnp.int32), start, this, cu,
                      jnp.asarray([0, 1, 2], jnp.int32), s.chunk_size)
    meta.update(pos=start[meta["t2b"]] + meta["off"], bt=bt)
    sel, counts = ms._select(s, q, meta, ck, 1)
    lists = []
    for b in range(2):
        n = int(start[b]) + 1
        keys = np.asarray(ck[1, bt[b]]).reshape(mb * per, hkv, d)
        closed = (n - 4) // 2 + 1
        for h in range(hkv):
            lg = np.einsum("gd,cd->gc", np.asarray(q[b]).reshape(
                hkv, g, d)[h], keys[:closed, h]) / 4.0
            p = np.exp(lg - lg.max(-1, keepdims=True))
            p = (p / p.sum(-1, keepdims=True)).sum(0)
            lo = (n - 1 - 15) // 8
            score = {m: max(p[j] for j in range(closed)
                            if 2 * j <= 8 * m + 7 and 2 * j + 3 >= 8 * m)
                     for m in range(1, lo)}
            best = sorted(score, key=lambda m: (-score[m], m))[:2]
            want = sorted({0, *best, *range(lo, (n - 1) // 8 + 1)})
            got = sorted(np.asarray(sel["sel"][b, h])[
                :int(sel["n_sel"][b, h])].tolist())
            assert got == want, (b, h)
            assert np.flatnonzero(np.asarray(
                sel["page_mask"][b, h])).tolist() == want
            lists.append(best)
    assert np.asarray(sel["listed"]).tolist() == [1, 1, 0]
    assert lists[0] != lists[2] or lists[1] != lists[3]
    # (query, KV head) page visits: selected = walked for rows of one token
    assert int(counts[0]) == int(counts[1]) == int(jnp.sum(sel["n_sel"]))
    assert int(counts[2]) == hkv * (-(-91 // 8) + -(-68 // 8))


def test_a_preempted_and_resumed_request_agrees():
    """A pool too small for two long requests: the newer one is pre-empted
    (pages, slot, and with the pages its compressed keys given up),
    re-prefilled from position 0, and serves what it serves alone."""
    model = make_model()
    ps = prompts(50, 50, seed=5)
    alone = []
    for p in ps:
        eng = make_engine(model)
        eng.add_request(p, max_new_tokens=24)
        alone.append(eng.run_to_completion()[0])
    before = counters().get("serving/preemptions", 0)
    eng = make_engine(model, num_blocks=17)       # 16 pages of 8 tokens
    for p in ps:
        eng.add_request(p, max_new_tokens=24)
    out = eng.run_to_completion()
    assert counters()["serving/preemptions"] > before
    assert [out[0], out[1]] == alone
    assert len(eng._free_slots) == 3


def test_decode_rows_past_dense_len_walk_what_they_chose():
    """Steps that hold rows of one token past dense_len only: the walk
    reads each row's list and no more (walked = selected, fewer than the
    context's pages), and the step span's `sparse_pages_walked`, known on
    the host from positions alone, is the device's count a layer."""
    from paddle_tpu.profiler import tracing

    model = make_model()
    eng = make_engine(model)
    for p in prompts(70, 80, seed=2):
        eng.add_request(p, max_new_tokens=12)
    while any(r.cached + r.ahead < len(r.prompt) for r in eng.pending()):
        eng.step()
    eng.settle()
    before = counters()
    tracing.clear_ring()
    for _ in range(6):
        eng.step()
    eng.settle()
    c = {k: v - before.get(k, 0) for k, v in counters().items()
         if k.startswith("serving/sparse")}
    assert c["serving/sparse_pages_walked"] \
        == c["serving/sparse_pages_selected"] > 0
    assert c["serving/sparse_pages_selected"] \
        < c["serving/sparse_pages_context"]
    assert c["serving/sparse_dense_rows"] == 0
    spans = [s["args"]["sparse_pages_walked"] for s in tracing.ring_spans()
             if s["name"] == "serving::step"
             and "sparse_pages_walked" in (s.get("args") or {})]
    assert sum(spans) * model.spec.count(ms.SPARSE) \
        == c["serving/sparse_pages_walked"]
    eng.run_to_completion()


def test_fetched_slabs_over_the_least_count_the_wide_tiles():
    """`serving/sparse_slabs_fetched` over `serving/sparse_slabs_least`: a
    full chunk of 160 tokens has its row's cached pages fetched once a wide
    query tile of 64 tokens, 3 times; a row of one token past `dense_len`
    reads its list once. The least is what the step spans carry
    (`walked_slabs`, from positions alone), a block-sparse layer."""
    from paddle_tpu.ops.pallas.sparse_paged_attention import _WIDE_TOKENS
    from paddle_tpu.profiler import tracing

    spec = MiniCPMSalaSpec.from_config(
        SIZES, published_layers=4, chunk_size=8, dtype="float32",
        **dict(SPARSE, window_size=160, dense_len=176))
    model = MiniCPMSala(spec, init_params(spec, seed=3, std=0.2))
    eng = make_engine(model, token_budget=160, max_blocks_per_seq=48)
    layers, hkv, bs = spec.count(ms.SPARSE), 2, 8

    def delta(before):
        return {k[len("serving/sparse_"):]: v - before.get(k, 0)
                for k, v in counters().items()
                if k.startswith("serving/sparse_slabs")}

    def span_slabs():
        return sum(s["args"]["sparse_pages_walked"]
                   for s in tracing.ring_spans()
                   if s["name"] == "serving::step"
                   and "sparse_pages_walked" in (s.get("args") or {}))

    (p,) = prompts(320, seed=4)
    before = counters()
    tracing.clear_ring()
    eng.add_request(p, max_new_tokens=6)
    while any(r.cached + r.ahead < len(r.prompt) for r in eng.pending()):
        eng.step()
    eng.settle()
    c = delta(before)
    # the second chunk alone has pages cached: 160 positions, 20 pages
    assert c["slabs_least"] == layers * hkv * (160 // bs) \
        == layers * span_slabs()
    assert c["slabs_fetched"] == c["slabs_least"] * -(-160 // _WIDE_TOKENS)
    before = counters()
    tracing.clear_ring()
    for _ in range(4):
        eng.step()
    eng.settle()
    c = delta(before)
    assert c["slabs_fetched"] == c["slabs_least"] == layers * span_slabs() \
        > 0
    eng.run_to_completion()


def test_what_a_recurrent_state_rules_out_is_refused():
    with pytest.raises(ValueError, match="state-space layer"):
        make_engine(prefix_cache=True)
    with pytest.raises(ValueError, match="state-space layer"):
        make_engine().decode_run(4)
    # a step longer than the local window, or pages that are not blocks
    eng = make_engine(token_budget=24)
    eng.add_request(prompts(30)[0], max_new_tokens=2)
    with pytest.raises(ValueError, match="local window"):
        eng.step()


def test_phase_map_names_the_new_scopes():
    eng = make_engine()
    for p in prompts(12, 40):
        eng.add_request(p, max_new_tokens=3)
    eng.run_to_completion()
    blocks = {v[1] for v in eng.phase_map("serving_step").values() if v}
    assert {"sparse_select", "kv_compress", "linear_attention",
            "attention", "mlp", "embed", "head"} <= blocks


# -- kernels, interpret mode -------------------------------------------------

def test_state_update_kernel_at_groups_equal_heads(monkeypatch):
    """`ssm_state_update` with every head its own B and C (lightning
    attention's 32 heads of [128, 128]) against the jnp reference: blocks
    of 16 heads, B and C as the block's rows."""
    from paddle_tpu.ops.pallas import ssm_state_update as m

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    L, S, H, P, N, R = 2, 4, 32, 128, 128, 3
    k = jax.random.split(jax.random.key(0), 5)
    state = jax.random.normal(k[0], (L, S, H, P, N), jnp.float32)
    x = jax.random.normal(k[1], (R, H, P), jnp.bfloat16)
    dt = jnp.ones((R, H), jnp.float32)
    a = -jnp.asarray(make_model().spec.slopes * 8, jnp.float32)
    b = jax.random.normal(k[2], (R, H, N), jnp.bfloat16)
    c = jax.random.normal(k[3], (R, H, N), jnp.bfloat16)
    d = jnp.zeros((H,))
    slots = jnp.array([2, 0, 1], jnp.int32)
    active = jnp.array([1, 0, 1], jnp.int32)
    reset = jnp.array([0, 0, 1], jnp.int32)
    args = (state, x, dt, a, b, c, d, slots, active, reset)
    assert m.use_kernel(state)
    s1, y1 = m.ssm_state_update(*args, layer_idx=1)
    s2, y2 = m.ssm_state_update_ref(*args, layer_idx=1)
    np.testing.assert_allclose(y1, y2, atol=2e-4, rtol=1e-5)
    np.testing.assert_allclose(s1[:, :3], s2[:, :3], atol=1e-5)
    np.testing.assert_array_equal(s1[0], state[0])
    np.testing.assert_array_equal(s1[1, 0], state[1, 0])   # inactive row


def test_sparse_walk_of_the_kernel_against_the_masked_reference(
        monkeypatch):
    """`sparse_paged_attention` in interpret mode: a row of one token
    walking its list, a chunk row walking all its pages under a mask that
    differs a token, a row under the mask of all ones, the padding row;
    against the gathered formulation over the written caches."""
    from paddle_tpu.ops.pallas import kv_page_write as kw
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.ops.pallas import sparse_paged_attention as spa

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    hq, hkv, d, bs, mb, nb = 4, 2, 128, 8, 24, 80
    starts = np.array([150, 91, 20, 0], np.int32)
    this = np.array([1, 7, 1, 7], np.int32)         # last: padding
    cu = np.concatenate([[0], np.cumsum(this)]).astype(np.int32)
    t = int(cu[-1])
    rng = np.random.default_rng(1)
    kc = jnp.asarray(rng.normal(size=(2, nb, hkv, bs, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(2, nb, hkv, bs, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(t, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(t, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(t, hkv, d)), jnp.float32)
    bt = np.zeros((4, mb), np.int32)
    bt[:3] = rng.permutation(np.arange(1, nb))[:3 * mb].reshape(3, mb)
    # the chunk row's tokens each choose their own pages (the local ones
    # always); the listed row a list a KV head
    mask = np.ones((t, hkv, mb), bool)
    for tok in range(1, 8):
        mask[tok] = rng.random((hkv, mb)) < 0.4
        mask[tok, :, 10:] = True
    sel = np.zeros((4, hkv, 9), np.int32)
    n_sel = np.zeros((4, hkv), np.int32)
    sel[0, 0, :6], n_sel[0, 0] = [0, 17, 18, 5, 9, 2], 6
    sel[0, 1, :5], n_sel[0, 1] = [0, 16, 17, 18, 11], 5
    listed = np.array([1, 0, 0, 0], np.int32)
    full = spa.page_mask_of_lists(
        jnp.asarray(mask), jnp.asarray(sel), jnp.asarray(n_sel),
        jnp.asarray(listed), jnp.asarray(cu[:-1]))
    assert np.flatnonzero(np.asarray(full[0, 1])).tolist() \
        == [0, 11, 16, 17, 18]
    assert pa.use_kernel(q, kc)
    got = spa.sparse_paged_attention(
        q, k, v, kc, vc, jnp.asarray(bt), jnp.asarray(starts),
        jnp.asarray(cu), jnp.asarray(listed), jnp.asarray(sel),
        jnp.asarray(n_sel), jnp.asarray(mask), layer_idx=1)
    kc2, vc2 = kw.kv_page_write_ref(kc, vc, k, v, jnp.asarray(bt),
                                    jnp.asarray(starts), jnp.asarray(cu),
                                    layer_idx=1)
    want = pa.paged_attention_ref(q, kc2, vc2, jnp.asarray(bt),
                                  jnp.asarray(starts), jnp.asarray(cu),
                                  layer_idx=1, page_mask=full)
    np.testing.assert_allclose(np.asarray(got[:9]), np.asarray(want[:9]),
                               atol=2e-5)
    # and it is the mask that decides: without it the chunk row differs
    dense = pa.paged_attention_ref(q, kc2, vc2, jnp.asarray(bt),
                                   jnp.asarray(starts), jnp.asarray(cu),
                                   layer_idx=1)
    assert float(jnp.abs(dense[1:8] - want[1:8]).max()) > 1e-3
    assert float(jnp.abs(dense[0] - want[0]).max()) > 1e-3


# -- the two walks of the kernel, one set of shapes (one lowering) -----------
# rows as (cached positions, tokens of this step, what the tokens attend):
# "list" a listed row of one token, "ones" every page (a row under
# `dense_len`), "random" a mask a token, "straddle" ones for the first 40
# tokens and a mask a token for the rest. Pages of 8 keys: a key tile of the
# wide walk is 128 pages, one 128-lane piece of the mask.
_WALK = dict(hq=4, hkv=2, d=128, bs=8, mb=160, rows=6, t=150, max_sel=12)
WALKS = {
    "chunk-under-dense_len": [(700, 100, "ones")],
    "chunk-straddling-dense_len": [(700, 100, "straddle")],
    "chunk-past-dense_len": [(1100, 100, "random")],
    # 70 tokens from token 1 on: two wide tiles, neither whole; 1,093
    # cached positions: 137 pages (a key tile and nine pages), the last one
    # partly filled
    "ragged-chunk-and-context": [(150, 1, "list"), (1093, 70, "random")],
    "two-chunks-a-listed-row-between": [
        (150, 1, "list"), (700, 60, "random"), (300, 1, "list"),
        (400, 50, "random"), (90, 1, "list")],
    "one-token-rows-under-dense_len": [
        (150, 1, "list"), (333, 1, "ones"), (20, 1, "ones")],
    "no-chunk": [(150, 1, "list"), (300, 1, "list")],
    "no-listed-row": [(700, 100, "random"), (40, 30, "ones")],
}


def _walk_case(rows, seed=0):
    """The kernel's operands for `rows` at `_WALK`'s shapes: (q, k, v, kc,
    vc, bt, starts, cu, listed, sel, n_sel, mask), tokens no row owns at
    the end of the pack."""
    w = _WALK
    hkv, bs, mb, n_rows, t = w["hkv"], w["bs"], w["mb"], w["rows"], w["t"]
    nb = n_rows * mb + 1
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    kc = jnp.asarray(rng.normal(size=(2, nb, hkv, bs, w["d"])), f32)
    vc = jnp.asarray(rng.normal(size=(2, nb, hkv, bs, w["d"])), f32)
    q = jnp.asarray(rng.normal(size=(t, w["hq"], w["d"])), f32)
    k = jnp.asarray(rng.normal(size=(t, hkv, w["d"])), f32)
    v = jnp.asarray(rng.normal(size=(t, hkv, w["d"])), f32)
    bt = rng.permutation(np.arange(1, nb)).reshape(n_rows, mb) \
        .astype(np.int32)
    starts = np.zeros(n_rows, np.int32)
    this = np.zeros(n_rows, np.int32)
    listed = np.zeros(n_rows, np.int32)
    sel = np.zeros((n_rows, hkv, w["max_sel"]), np.int32)
    n_sel = np.zeros((n_rows, hkv), np.int32)
    mask = np.ones((t, hkv, mb), bool)
    tok = 0
    for b, (start, n, kind) in enumerate(rows):
        starts[b], this[b] = start, n
        pages = -(-start // bs)
        if kind == "list":
            listed[b] = 1
            for h in range(hkv):
                m = int(rng.integers(3, w["max_sel"]))
                lst = [0, pages - 1] + rng.choice(
                    np.arange(1, pages - 1), m - 2, replace=False).tolist()
                sel[b, h, :m], n_sel[b, h] = lst, m
        elif kind != "ones":
            chosen = rng.random((n, hkv, mb)) < 0.4
            chosen[:, :, max(pages - 3, 0):] = True
            if kind == "straddle":
                chosen[:40] = True
            mask[tok:tok + n] = chosen
        tok += n
    cu = np.concatenate([[0], np.cumsum(this)]).astype(np.int32)
    return (q, k, v, kc, vc) + tuple(
        jnp.asarray(x) for x in (bt, starts, cu, listed, sel, n_sel, mask))


@pytest.mark.parametrize("case", sorted(WALKS))
def test_wide_and_list_walks_against_the_masked_reference(case, monkeypatch):
    """`sparse_paged_attention` in interpret mode, a row's walk chosen by
    `listed` on the device: chunks under, across and past `dense_len` in
    wide query tiles and key tiles that neither the chunk nor the context
    fills, rows of one token on either walk, each walk with nothing to do;
    against the gathered formulation over the written caches. A token no
    row owns reads 0."""
    from paddle_tpu.ops.pallas import kv_page_write as kw
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.ops.pallas import sparse_paged_attention as spa

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    q, k, v, kc, vc, bt, starts, cu, listed, sel, n_sel, mask = \
        _walk_case(WALKS[case])
    full = spa.page_mask_of_lists(mask, sel, n_sel, listed, cu[:-1])
    got = spa.sparse_paged_attention(q, k, v, kc, vc, bt, starts, cu, listed,
                                     sel, n_sel, mask, layer_idx=1)
    kc2, vc2 = kw.kv_page_write_ref(kc, vc, k, v, bt, starts, cu,
                                    layer_idx=1)
    want = pa.paged_attention_ref(q, kc2, vc2, bt, starts, cu, layer_idx=1,
                                  page_mask=full)
    n = int(cu[-1])
    np.testing.assert_allclose(np.asarray(got[:n]), np.asarray(want[:n]),
                               atol=2e-5)
    assert not np.asarray(got[n:]).any()
    if any(kind in ("random", "straddle") for _, _, kind in WALKS[case]):
        dense = pa.paged_attention_ref(q, kc2, vc2, bt, starts, cu,
                                       layer_idx=1)
        assert float(jnp.abs(dense[:n] - want[:n]).max()) > 1e-3


def test_list_walk_is_bit_for_bit_the_kernel_before_the_wide_tiles(
        monkeypatch):
    """A decode-only step (listed rows of one token, no other row): the
    kernel's output equals, bit for bit, that of the kernel as PR 34 left
    it (`tests/sparse_walk_pr34.py`, every row in narrow query blocks),
    both interpreted on this machine."""
    import sparse_walk_pr34 as before

    from paddle_tpu.ops.pallas import sparse_paged_attention as spa

    rows = [(150, 1, "list"), (1093, 1, "list"), (700, 1, "list"),
            (90, 1, "list"), (1270, 1, "list")]
    args = _walk_case(rows, seed=5)
    layer = jnp.full((1,), 1, jnp.int32)
    got = spa._sparse_call(*args, layer, interpret=True)
    was = before._sparse_call(*args, layer, interpret=True)
    n = len(rows)
    assert float(jnp.abs(was[:n]).max()) > 0.1
    np.testing.assert_array_equal(np.asarray(got[:n]), np.asarray(was[:n]))


def test_engine_through_the_kernels_in_interpret_mode(monkeypatch):
    """The whole engine with every kernel interpreted (heads of 128, so
    that pages and states tile): the reference's logits, and no kernel
    gives way."""
    monkeypatch.setenv("PT_USE_PALLAS", "1")
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    wide = dict(SIZES, head_dim=128, lightning_head_dim=128)
    spec = MiniCPMSalaSpec.from_config(wide, published_layers=4,
                                       chunk_size=8, dtype="float32",
                                       **SPARSE)
    model = MiniCPMSala(spec, init_params(spec, seed=3, std=0.2))
    eng = make_engine(model)
    (p,) = prompts(60, seed=6)
    before = counters()
    (toks,), seen = serve_with_logits(eng, [p], 6, monkeypatch)
    from benchmark.reference import minicpm_sala_serve as ref

    lg = np.asarray(ref.logits_at(model.params, p + toks, len(p) - 1,
                                  wide))[:6]
    for i, row in enumerate(seen):
        np.testing.assert_allclose(row[0], lg[i], atol=TOL)
    c = counters()
    for name in ("ssm_state_update", "paged_attention"):
        key = f"pallas/reference_dispatch/{name}"
        assert c.get(key, 0) == before.get(key, 0)
    assert c["serving/paged_kernel_steps"] \
        > before.get("serving/paged_kernel_steps", 0)


# -- the lift of the chunked scan ------------------------------------------

def _chunk_scan_before_the_lift(s, ssm, li, xs, dt, a, b, c, d, blocks,
                                n_blocks):
    """`models/nemotron_h.py::_chunk_scan` as it stood before it moved to
    `models/chunk_scan.py` (kept here, verbatim but for this docstring, so
    that the lift is held to it bit for bit on any machine)."""
    f32 = jnp.float32
    t, hm, pd = xs.shape
    g, n = b.shape[1], b.shape[2]
    q, hb = s.chunk_size, hm // g
    pad = ((0, q), (0, 0), (0, 0))
    xs_p, b_p, c_p = (jnp.pad(v, pad) for v in (xs, b, c))
    dt_p = jnp.pad(dt, ((0, q), (0, 0)))
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]

    def block(j, carry):
        ssm, h, y = carry
        blk = {k: v[j] for k, v in blocks.items()}
        off = blk["off"]
        take = lambda v: jax.lax.dynamic_slice_in_dim(v, off, q, 0)
        keep = jnp.arange(q) < blk["len"]
        xq = take(xs_p).astype(f32)
        bq, cq = take(b_p), take(c_p)
        dtq = jnp.where(keep[:, None], take(dt_p), 0.0)
        h0 = jax.lax.dynamic_slice(
            ssm, (li, blk["read"], 0, 0, 0), (1, 1, hm, pd, n))[0, 0]
        h = jnp.where(blk["first"],
                      jnp.where(blk["fresh"], 0.0, h0), h)
        cs = jnp.cumsum(dtq * a, axis=0)
        cb = jnp.einsum("qgn,sgn->gqs", cq, bq,
                        preferred_element_type=f32)
        decay = jnp.where(causal[None],
                          jnp.exp(cs.T[:, :, None] - cs.T[:, None, :]), 0.0)
        m = jnp.repeat(cb, hb, axis=0) * decay * dtq.T[:, None, :]
        yq = jnp.einsum("hqs,shp->qhp", m, xq)
        yq = yq + jnp.exp(cs)[:, :, None] * jnp.einsum(
            "qgn,gipn->qgip", cq.astype(f32),
            h.reshape(g, hb, pd, n)).reshape(q, hm, pd)
        yq = yq + d[None, :, None] * xq
        to_end = jnp.exp(cs[-1][None] - cs) * dtq
        h = jnp.exp(cs[-1])[:, None, None] * h + jnp.einsum(
            "sgip,sgn->gipn", (to_end[:, :, None] * xq).reshape(
                q, g, hb, pd), bq.astype(f32)).reshape(hm, pd, n)
        ssm = jax.lax.dynamic_update_slice(
            ssm, h[None, None], (li, blk["write"], 0, 0, 0))
        cur = jax.lax.dynamic_slice_in_dim(y, off, q, 0)
        y = jax.lax.dynamic_update_slice_in_dim(
            y, jnp.where(keep[:, None, None], yq, cur), off, 0)
        return ssm, h, y

    ssm, _, y = jax.lax.fori_loop(
        0, n_blocks, block, (ssm, jnp.zeros((hm, pd, n), f32),
                             jnp.zeros((t + q, hm, pd), f32)))
    return ssm, y[:t]


def test_nemotron_step_is_bit_identical_after_the_lift(monkeypatch):
    """One module, two importers: Nemotron's mixed step (a chunk beside
    rows of one token, a fixed seed) through the shared `chunk_scan` gives,
    bit for bit, what it gave through its own `_chunk_scan`."""
    from paddle_tpu.models import chunk_scan as cs
    from paddle_tpu.models import nemotron_h as nh
    import test_nemotron_h as tn

    assert nh.chunk_scan is cs.chunk_scan is ms.chunk_scan
    assert nh.rows_of is cs.rows_of is ms.rows_of
    model = tn.make_model()
    ps = tn.prompts(21, 5, 37, seed=13)

    def serve():
        object.__setattr__(model, "_serving_shared", None)
        eng = tn.make_engine(model, token_budget=16)
        for p in ps:
            eng.add_request(p, max_new_tokens=5)
        out = eng.run_to_completion()
        return out, [np.asarray(v) for v in eng._row_state.values()], \
            np.asarray(eng._kc)

    after = serve()
    monkeypatch.setattr(
        nh, "chunk_scan", lambda q, ssm, li, *a: _chunk_scan_before_the_lift(
            model.spec, ssm, li, *a))
    before = serve()
    assert after[0] == before[0]
    for x, y in zip(after[1] + [after[2]], before[1] + [before[2]]):
        np.testing.assert_array_equal(x, y)


# sha256 of the jaxpr of `ssm_state_update` at the hybrid cell's shapes
# (5 layers, 129 slots, 128 heads of [64, 128] in 8 groups: the pallas_call
# with its kernel, grid and block mappings), taken on the parent of the PR
# that gave the kernel blocks of heads (jax 0.9.0): where heads share their
# group's B and C the program is the one it was.
_SSM_JAXPR_JAX = "0.9.0"
_SSM_JAXPR = "7b12f3f403688aa412e14c543efdeaf5ef5f99f5e162f3684007a5f49aa2bb8e"


@pytest.mark.skipif(jax.__version__ != _SSM_JAXPR_JAX,
                    reason="the hash was taken under another jax")
def test_state_update_kernel_is_unchanged_at_nemotron_shapes(monkeypatch):
    import hashlib

    from paddle_tpu.ops.pallas import ssm_state_update as ssu

    monkeypatch.setenv("PT_USE_PALLAS", "1")
    L, S, H, P, N, G, R = 5, 129, 128, 64, 128, 8, 129

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt)

    f32, bf16 = jnp.float32, jnp.bfloat16
    text = str(jax.make_jaxpr(
        lambda *a: ssu.ssm_state_update(*a, layer_idx=3))(
            s((L, S, H, P, N), f32), s((R, H, P), bf16), s((R, H), f32),
            s((H,), f32), s((R, G, N), bf16), s((R, G, N), bf16),
            s((H,), f32), s((R,)), s((R,)), s((R,))))
    assert "pallas_call" in text
    assert hashlib.sha256(text.encode()).hexdigest() == _SSM_JAXPR, \
        "ssm_state_update's program at Nemotron's shapes changed"
