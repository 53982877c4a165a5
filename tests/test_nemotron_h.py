"""The hybrid model through `ServingEngine` at a small size (hidden 64,
pattern MEMEMEMEM*E, 16 routed experts of which 4 held, top 3, seeded
float32 weights), against the benchmark's plain reference: a state kept by
row slot beside pages, the share of the experts, the state-update kernel,
and what the engine refuses for such a model."""
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (PagedCausalLM, PagedServingConfig,
                                          ServingEngine)
from paddle_tpu.models.nemotron_h import NemotronH, NemotronHSpec

SIZES = dict(
    vocab_size=128, hidden_size=64, hybrid_override_pattern="MEMEMEMEM*E",
    layer_norm_epsilon=1e-5, mamba_num_heads=8, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    n_routed_experts=16, num_experts_per_tok=3, moe_latent_size=32,
    moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
    routed_scaling_factor=2.5, norm_topk_prob=True)
HELD = (4, 4)


def make_params(spec, seed=0):
    key = jax.random.key(seed)
    out = {}
    for i, (name, (shape, dt)) in enumerate(sorted(
            spec.param_shapes().items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("router_bias"):      # a bias that does select
            out[name] = 0.3 * jax.random.normal(k, shape, dt)
        elif len(shape) == 1:
            out[name] = jnp.ones(shape, dt)
        else:
            out[name] = (0.1 * jax.random.normal(k, shape)).astype(dt)
    return out


def make_model(held=HELD, **over):
    spec = NemotronHSpec.from_config(SIZES, held=held, dtype="float32",
                                     **over)
    return NemotronH(spec, make_params(spec))


def make_engine(model=None, **over):
    kw = dict(vocab_size=128, hidden_size=64, num_layers=11, num_heads=4,
              num_kv_heads=2, block_size=8, num_blocks=65, max_batch=4,
              max_blocks_per_seq=8, token_budget=32, dtype="float32")
    kw.update(over)
    model = model or make_model()
    return ServingEngine.from_model(model, PagedServingConfig(**kw), seed=1)


def reference_logits(model, tokens, first):
    from benchmark.reference import nemotron_h_serve as ref

    first_held, count = model.spec.held
    cfg = dict(SIZES, experts_held={"first": first_held, "count": count,
                                    "of": SIZES["n_routed_experts"]})
    return np.asarray(ref.logits_at(model.params, tokens, first, cfg))


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, n).tolist() for n in lengths]


def test_prefill_then_decode_agree_with_the_reference_by_logits():
    """Chunked prefill (40 and 33 tokens against a budget of 32), rows of
    one token, a prompt of one token: every served token is the plain
    reference's best at its position, and the probe's logits are its."""
    from benchmark import compare

    ps = prompts(20, 5, 40, 1, 33)
    model = make_model()
    eng = make_engine(model)
    for p in ps:
        eng.add_request(p, max_new_tokens=6)
    out = eng.run_to_completion()
    for rid, p in enumerate(ps):
        lg = reference_logits(model, p + out[rid], len(p) - 1)[:6]
        assert out[rid] == lg.argmax(-1).tolist()
        assert compare.served_gap(lg, out[rid]) < 1e-4
    probe = eng.probe_logits(ps[0])
    np.testing.assert_allclose(
        probe, reference_logits(model, ps[0], len(ps[0]) - 1)[0], atol=1e-4)
    assert len(eng._free_slots) == 4 and not eng.pending()


def test_three_chunks_across_steps_equal_one_chunk():
    """The state and the conv tail carried across chunked-prefill steps:
    a prompt of 40 prefilled 16 + 16 + 8 leaves the slot what one chunk of
    40 leaves it, and the same tokens follow."""
    model = make_model()
    (p,) = prompts(40, seed=3)
    got = {}
    for budget in (16, 64):
        eng = make_engine(model, token_budget=budget)
        rid = eng.add_request(p, max_new_tokens=5)
        steps, r = 0, eng._requests[rid]
        while r.cached + r.ahead < len(p):      # dispatched, not fetched
            eng.step()
            steps += 1
        slot = r.slot
        got[budget] = (steps, np.asarray(eng._row_state["ssm"][:, slot]),
                       np.asarray(eng._row_state["conv"][:, slot]),
                       eng.run_to_completion()[rid])
    assert (got[16][0], got[64][0]) == (3, 1)
    np.testing.assert_allclose(got[16][1], got[64][1], atol=1e-5)
    np.testing.assert_allclose(got[16][2], got[64][2], atol=1e-5)
    assert got[16][3] == got[64][3]


def test_every_row_of_a_step_may_hold_a_chunk():
    """No limit on the rows with a chunk: four prompts fill one step's 32
    tokens as 9 + 9 + 9 + 5, seven blocks of 8 where the step has room for
    32 / 8 + 4, and each serves what it serves alone."""
    from paddle_tpu.profiler import metrics

    model = make_model()
    ps = prompts(9, 9, 9, 5, seed=11)
    alone = []
    for p in ps:
        eng = make_engine(model)
        eng.add_request(p, max_new_tokens=6)
        alone.append(eng.run_to_completion()[0])
    eng = make_engine(model)
    for p in ps:
        eng.add_request(p, max_new_tokens=6)
    before = metrics.snapshot()["counters"].get("serving/ssm_rows_chunk", 0)
    eng.step()
    assert metrics.snapshot()["counters"]["serving/ssm_rows_chunk"] \
        == before + 4
    out = eng.run_to_completion()
    assert [out[i] for i in range(4)] == alone


def test_a_preempted_and_resumed_request_agrees():
    """A pool too small for two long requests: the newer one is pre-empted
    (pages and slot given up), re-prefilled from a zeroed slot, and serves
    what it serves alone."""
    from paddle_tpu.profiler import metrics

    model = make_model()
    ps = prompts(30, 30, seed=5)
    alone = []
    for p in ps:
        eng = make_engine(model)
        eng.add_request(p, max_new_tokens=20)
        alone.append(eng.run_to_completion()[0])
    before = metrics.snapshot()["counters"].get("serving/preemptions", 0)
    eng = make_engine(model, num_blocks=11)        # 10 pages of 8 tokens
    for p in ps:
        eng.add_request(p, max_new_tokens=20)
    out = eng.run_to_completion()
    assert metrics.snapshot()["counters"]["serving/preemptions"] > before
    assert [out[0], out[1]] == alone
    assert len(eng._free_slots) == 4


def test_a_reused_slot_starts_from_zero():
    """One slot: the second request takes the slot the first left full."""
    model = make_model()
    a, b = prompts(25, 18, seed=7)
    eng = make_engine(model, max_batch=1)
    eng.add_request(a, max_new_tokens=4)
    eng.run_to_completion()
    assert float(jnp.abs(eng._row_state["ssm"][:, 0]).max()) > 0
    rid = eng.add_request(b, max_new_tokens=6)
    reused = eng.run_to_completion()[rid]
    fresh = make_engine(model, max_batch=1)
    fresh.add_request(b, max_new_tokens=6)
    assert reused == fresh.run_to_completion()[0]


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all the shares, with the shared expert counted
    once, equal the uncut layer: the expert layer of four chips of four
    experts each against one chip that holds all sixteen."""
    from paddle_tpu.models import nemotron_h as nh

    whole = make_model(held=(0, 16))
    w = {k.split(".", 2)[2]: v for k, v in whole.params.items()
         if k.startswith("layers.1.")}
    u = jax.random.normal(jax.random.key(9), (24, 64), jnp.float32)
    valid = jnp.arange(24) < 20
    y_all, counts = nh._experts(whole.spec, w, u, valid)
    shared = nh.relu2(u @ w["shared_w1"]) @ w["shared_w2"]
    parts = []
    for first in (0, 4, 8, 12):
        spec = NemotronHSpec.from_config(SIZES, held=(first, 4),
                                         dtype="float32")
        mine = dict(w, w1=w["w1"][first:first + 4],
                    w2=w["w2"][first:first + 4])
        y, c = nh._experts(spec, mine, u, valid)
        parts.append((y - shared, int(c[0])))
    np.testing.assert_allclose(sum(p for p, _ in parts) + shared, y_all,
                               atol=1e-5)
    # every pair of a valid token is held by exactly one share
    assert sum(n for _, n in parts) == int(counts[0]) == 20 * 3
    assert int(counts[1]) == 20
    # and a share alone is NOT the layer
    assert float(jnp.abs(parts[0][0] + shared - y_all).max()) > 1e-3


def test_router_weights_are_normalised_over_all_the_chosen():
    from paddle_tpu.incubate.distributed.models.moe import (
        sigmoid_topk_route)

    u = jax.random.normal(jax.random.key(1), (6, 64))
    wg = 0.2 * jax.random.normal(jax.random.key(2), (64, 16))
    bias = jnp.zeros((16,)).at[3].set(10.0)      # selects, does not weigh
    idx, w = sigmoid_topk_route(u, wg, bias, 3, scale=5.0)
    assert bool(jnp.all(jnp.any(idx == 3, axis=-1)))
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 5.0, rtol=1e-6)
    s = jax.nn.sigmoid(u @ wg)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(5.0 * jnp.take_along_axis(s, idx, -1)
                                  / jnp.take_along_axis(s, idx, -1).sum(
                                      -1, keepdims=True)), rtol=1e-5)


def test_state_update_kernel_in_interpret_mode(monkeypatch):
    from paddle_tpu.ops.pallas import ssm_state_update as m
    from paddle_tpu.profiler import metrics

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    L, S, H, P, N, G, R = 2, 5, 4, 8, 128, 2, 4
    k = jax.random.split(jax.random.key(0), 6)
    state = jax.random.normal(k[0], (L, S, H, P, N), jnp.float32)
    x = jax.random.normal(k[1], (R, H, P), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[2], (R, H)))
    a = -jnp.exp(0.1 * jax.random.normal(k[3], (H,)))
    b = jax.random.normal(k[4], (R, G, N), jnp.bfloat16)
    c = jax.random.normal(k[5], (R, G, N), jnp.bfloat16)
    d = jnp.ones((H,))
    slots = jnp.array([2, 0, 3, 1], jnp.int32)
    active = jnp.array([1, 0, 1, 1], jnp.int32)
    reset = jnp.array([0, 0, 1, 0], jnp.int32)
    args = (state, x, dt, a, b, c, d, slots, active, reset)
    assert m.use_kernel(state)
    s1, y1 = m.ssm_state_update(*args, layer_idx=1)
    s2, y2 = m.ssm_state_update_ref(*args, layer_idx=1)
    np.testing.assert_allclose(y1, y2, atol=1e-4)
    np.testing.assert_allclose(s1[:, :4], s2[:, :4], atol=1e-5)
    # the inactive row's slot and the other layer are left alone
    np.testing.assert_array_equal(s1[1, 0], state[1, 0])
    np.testing.assert_array_equal(s1[0, :4], state[0, :4])
    assert float(jnp.abs(y1[1]).max()) == 0.0
    # a state that does not tile gives way to the reference, counted
    before = metrics.snapshot()["counters"].get(
        "pallas/reference_dispatch/ssm_state_update", 0)
    assert not m.use_kernel(state[..., :16])
    assert metrics.snapshot()["counters"][
        "pallas/reference_dispatch/ssm_state_update"] == before + 1


def _refusals():
    from paddle_tpu.inference.speculative import NGramDrafter

    yield "prefix_cache", lambda: make_engine(prefix_cache=True)
    yield "cache_quant", lambda: make_engine(cache_quant="int8")
    yield "weight_stream", lambda: ServingEngine.from_model(
        make_model(), PagedServingConfig(dtype="float32"),
        weight_stream="int8")
    yield "set_drafter", lambda: make_engine().set_drafter(
        NGramDrafter(block_size=8), k=2)
    yield "decode_run", lambda: make_engine().decode_run(4)


@pytest.mark.parametrize("what", ["prefix_cache", "cache_quant",
                                  "weight_stream", "set_drafter",
                                  "decode_run"])
def test_what_a_recurrent_state_rules_out_is_refused(what):
    call = dict(_refusals())[what]
    with pytest.raises(ValueError, match="state-space layer"):
        call()


def test_migration_of_a_row_with_state_is_refused():
    from paddle_tpu.inference import disagg

    eng = make_engine()
    rid = eng.add_request(prompts(9)[0], max_new_tokens=4)
    eng.step()
    with pytest.raises(ValueError, match="state-space layer"):
        disagg.migrate_request(eng, rid, None, 1)


def test_phase_map_names_the_new_scopes():
    eng = make_engine()
    for p in prompts(12, 40):
        eng.add_request(p, max_new_tokens=3)
    eng.run_to_completion()
    blocks = {v[1] for v in eng.phase_map("serving_step").values() if v}
    assert {"ssm", "ssm_conv", "moe_route", "moe_experts", "moe_shared",
            "attention", "head"} <= blocks


# sha256 of the three step programs' lowered text at the tiny size below,
# taken on the parent of the PR that gave the engine its layer-kind state
# (jax 0.9.0): the refactor must leave PagedCausalLM's programs as they
# were. A jax upgrade that changes the text re-takes them (the function
# below prints what it finds). Re-taken by PR 30, which changed the programs
# on purpose: the page stacks are donated (the arguments carry the mark) and
# the scatter is `kv_page_write_ref`'s, after the attention where the kernels
# run and before it where they do not, as here.
_PAGED_PROGRAMS_JAX = "0.9.0"
_PAGED_PROGRAMS = {
    "serving_step": "c4ea4b14857d11047aef603043781cd2eae1e1f7fdd342e5fd049ff8ec80b443",
    "serving_fresh_prefill": "9a1a9ded5addc4840f53aa7e58ad14fce3f83abbd33e60a9d8a1c5346a9f1e5e",
    "serving_spec_verify": "7f5bff99c7ecfa27f55904a453e91babb033c4bbeffdb876789f11ed926e8fae",
}


def paged_program_hashes():
    cfg = PagedServingConfig(vocab_size=97, hidden_size=64, num_layers=2,
                             num_heads=4, num_kv_heads=2, ffn_size=128,
                             block_size=8, num_blocks=17, max_batch=2,
                             max_blocks_per_seq=4, token_budget=16)
    paddle.seed(3)
    model = PagedCausalLM(cfg)
    model.eval()
    eng = ServingEngine.from_model(model, cfg, seed=3)
    b1, t = cfg.max_batch + 1, cfg.token_budget
    i32 = np.int32
    args = (eng._params, eng._buffers, np.zeros(t, i32), np.zeros(b1, i32),
            np.zeros(b1, i32), np.zeros(b1, i32), np.zeros(b1 + 1, i32),
            np.zeros((b1, cfg.max_blocks_per_seq), i32), eng._kc, eng._vc)
    return {name: hashlib.sha256(
        fn.lower(*args).as_text().encode()).hexdigest()
        for name, fn in (("serving_step", eng._compiled),
                         ("serving_fresh_prefill", eng._compiled_fresh),
                         ("serving_spec_verify", eng._compiled_verify))}


@pytest.mark.skipif(jax.__version__ != _PAGED_PROGRAMS_JAX,
                    reason="the hashes were taken under another jax")
def test_paged_causal_lm_programs_are_unchanged():
    assert paged_program_hashes() == _PAGED_PROGRAMS


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(paged_program_hashes())
