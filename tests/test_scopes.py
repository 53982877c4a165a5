"""Names in the compiled programs (ISSUE 25 C): `profiler.scopes` labels,
the pass/block map read from the compiled text, the registry that outlives
the trainer and never holds the engine's model, the Pallas kernels' names,
and the profiler's ModelView over them. All on the CPU."""
import collections
import contextlib
import dataclasses
import gc
import re
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet import trainer as trainer_mod
from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.models import llama
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import rms_norm as rn
from paddle_tpu.ops.pallas import varlen_attention as va
from paddle_tpu.profiler import Profiler, SummaryView, scopes

TINY = dataclasses.replace(llama.LLAMA_PRESETS["debug"], num_hidden_layers=2)
BATCH = (2, 32)


def _trainer():
    return trainer_mod.HybridTrainer(
        TINY, build_mesh(devices=jax.devices()[:1]))


def _ids():
    return np.arange(BATCH[0] * BATCH[1], dtype=np.int32) \
        .reshape(BATCH) % TINY.vocab_size


@pytest.fixture(scope="module")
def compiled_text():
    return _trainer().lower(BATCH).compile().as_text()


def test_classify_reads_pass_and_block():
    c = scopes.classify
    assert c("jit(train_step)/jvp(pt.head_loss)/dot_general") \
        == ("forward", "head_loss")
    assert c("jit(train_step)/transpose(jvp())/while/body/closed_call/"
             "checkpoint/rematted_computation/pt.attention/mul") \
        == ("recompute", "attention")
    assert c("jit(train_step)/transpose(jvp())/while/body/closed_call/"
             "checkpoint/pt.mlp/jit(silu)/mul") == ("backward", "mlp")
    assert c("jit(train_step)/pt.adamw/sub") == ("optimizer", "adamw")
    assert c("jit(train_step)/pt.clip/mul") == ("optimizer", "clip")
    # the innermost scope names the block
    assert c("jit(serving_step)/pt.attention/pt.kv_gather/gather") \
        == ("forward", "kv_gather")
    assert c("jit(train_step)/jvp()/while/body/add") \
        == ("forward", scopes.NO_BLOCK)


def test_parse_phases_reads_multi_line_and_unnamed_instructions():
    text = """HloModule jit_f

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(f)/pt.mlp/mul"}
}

%fused_computation.1 (q: f32[8]) -> f32[16] {
  %buffer = f32[16]{0} custom-call(), custom_call_target="AllocateBuffer"
  %q = f32[8]{0} parameter(0)
  %zero = s32[] constant(0)
  ROOT %dus = f32[16]{0} dynamic-update-slice(%buffer, %q, %zero)
}

ENTRY %main (a: f32[8]) -> f32[16] {
  %a = f32[8]{0} parameter(0)
  %rms_norm.1 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"kernel":"rms_norm"
}}, metadata={op_name="jit(f)/transpose(jvp(pt.attention))/rms_norm"}
  %fusion.2 = f32[8]{0} fusion(%rms_norm.1), kind=kLoop, calls=%fused_computation
  %copy.3 = f32[8]{0} copy(%a)
  %gather.4 = (f32[8]{0}, s32[]) fusion(%a, %fusion.2), kind=kLoop, calls=%missing
  ROOT %assembled.5 = f32[16]{0} fusion(%copy.3, %gather.4), kind=kLoop, calls=%fused_computation.1
}
"""
    phases = scopes.parse_phases(text)
    assert phases["m"] == ("forward", "mlp")
    assert phases["rms_norm.1"] == ("backward", "attention")
    # no op_name of its own: the commonest phase inside what it calls
    assert phases["fusion.2"] == ("forward", "mlp")
    # nor there: its first named operand's (here through a tuple type)
    assert phases["gather.4"] == ("forward", "mlp")
    assert phases["assembled.5"] == ("forward", "mlp")
    # nothing names a parameter or a copy of one
    assert phases["a"] is None and phases["copy.3"] is None
    assert phases["dus"] is None
    # what was guessed is told apart from what an op_name said
    assert phases.inherited == {"fusion.2", "gather.4", "assembled.5"}
    assert scopes.instruction_name(
        "%rms_norm.1 = f32[8]{0} custom-call(f32[8]{0} %a), custom_call"
        "_target=\"tpu_custom_call\"") == "rms_norm.1"


def test_every_scope_label_is_in_the_compiled_step(compiled_text):
    for label in ("embed", "attention", "mlp", "head_loss", "clip", "adamw"):
        assert f"{scopes.PREFIX}{label}" in compiled_text, label
    by_pass = collections.Counter(
        p[0] for p in scopes.parse_phases(compiled_text).values() if p)
    for name in scopes.PASSES:
        assert by_pass[name] > 0, name
    blocks = {p for p in scopes.parse_phases(compiled_text).values() if p}
    assert {("recompute", "attention"), ("recompute", "mlp"),
            ("backward", "head_loss"), ("backward", "embed"),
            ("optimizer", "adamw")} <= blocks


def _opcodes(text):
    """Instructions of a compiled module's text by opcode."""
    kinds = collections.Counter()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?[\w.\-]+ = \S+ ([a-z][a-z0-9\-]*)\(",
                     line)
        if m:
            kinds[m.group(1)] += 1
    return kinds


def test_scopes_leave_the_optimised_program_alone(compiled_text,
                                                  monkeypatch):
    """Scopes are metadata: with `scope` a null context the compiled step
    has the same instructions, opcode by opcode."""
    from jax.experimental.compilation_cache import compilation_cache

    null = lambda name: contextlib.nullcontext()            # noqa: E731
    monkeypatch.setattr(scopes, "scope", null)
    monkeypatch.setattr(llama, "scope", null)
    # the persistent cache's key leaves metadata out: where an earlier test
    # of this worker switched it on (bench.main does), the scoped program's
    # entry would answer for the bare one, names and all
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        bare = _trainer().lower(BATCH).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert scopes.PREFIX + "attention" not in bare
    assert _opcodes(bare) == _opcodes(compiled_text)
    assert sum(_opcodes(bare).values()) > 100


def test_train_step_map_outlives_the_trainer():
    tr = _trainer()
    tr.step(_ids(), _ids())
    entry = scopes._named["train_step"]
    assert entry in scopes.live_programs()
    assert not any(isinstance(leaf, jax.Array)
                   for leaf in jax.tree.leaves(entry._args))
    params = weakref.ref(tr.params["embed"])
    step_fn = weakref.ref(tr._compiled)
    del tr
    gc.collect()
    assert params() is None            # the registry pins no array
    assert step_fn() is not None       # only the function, until asked
    phases = scopes.instruction_phases("train_step")
    assert {p[0] for p in phases.values() if p} == set(scopes.PASSES)
    assert scopes.instruction_phases("train_step") is phases   # parsed once
    gc.collect()
    assert step_fn() is None           # a built map lets the function go
    assert entry in scopes.live_programs()
    assert scopes.instruction_phases("never_registered") is None


def test_weak_registration_dies_with_its_owner():
    class Big:
        pass

    big = Big()
    alive = weakref.ref(big)

    def fn(x):
        with scopes.scope("mlp"):
            return x * (2.0 if big else 1.0)

    jitted = jax.jit(fn)
    x = jnp.ones(4)
    jitted(x)
    asked = scopes.register_program("probe_weak", jitted,
                                    scopes.abstract((x,)), weak=True)
    never = scopes.register_program("probe_weak", jitted,
                                    scopes.abstract((x,)), weak=True)
    assert scopes.instruction_phases("probe_weak") is None  # not by name
    assert ("forward", "mlp") in asked.phases().values()
    del jitted, fn, big
    gc.collect()
    assert alive() is None
    # the map that was built stays; the one never asked for is gone
    assert ("forward", "mlp") in asked.phases().values()
    assert never.phases() is None
    live = scopes.live_programs()
    assert asked in live and never not in live
    del asked, live
    gc.collect()
    assert not [p for p in scopes.live_programs()
                if p.name == "probe_weak"]


def test_engine_registers_weakly_and_offers_its_phase_map():
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              ServingEngine)

    paddle.seed(3)
    cfg = PagedServingConfig(vocab_size=97, hidden_size=32, num_layers=2,
                             num_heads=4, ffn_size=64, block_size=8,
                             num_blocks=32, max_batch=3,
                             max_blocks_per_seq=6, token_budget=16)
    model = PagedCausalLM(cfg)
    model.eval()
    eng = ServingEngine.from_model(model, cfg)
    assert eng.phase_map() is None                  # nothing has run yet
    eng.add_request(list(range(1, 21)), max_new_tokens=3)
    eng.run_to_completion()
    for name in ("serving_step", "serving_fresh_prefill"):
        entry = eng._programs[name, None]
        assert isinstance(entry._ref, weakref.ref), name
        assert not any(isinstance(leaf, jax.Array)
                       for leaf in jax.tree.leaves(entry._args)), name
        assert name not in scopes._named
    blocks = {p[1] for p in eng.phase_map().values() if p}
    assert {"embed", "attention", "kv_write", "kv_gather", "mlp",
            "head"} <= blocks
    fresh = {p[1] for p in eng.phase_map("serving_fresh_prefill").values()
             if p}
    assert "kv_write" in fresh and "kv_gather" not in fresh
    assert eng._compiled.__name__ == "serving_step"
    assert eng._compiled_fresh.__name__ == "serving_fresh_prefill"
    assert eng._compiled_verify.__name__ == "serving_spec_verify"


def _engine(hidden, layers, seed=3):
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              ServingEngine)

    paddle.seed(seed)
    cfg = PagedServingConfig(vocab_size=97, hidden_size=hidden,
                             num_layers=layers, num_heads=4,
                             ffn_size=2 * hidden, block_size=8,
                             num_blocks=32, max_batch=3,
                             max_blocks_per_seq=6, token_budget=16)
    model = PagedCausalLM(cfg)
    model.eval()
    return model, ServingEngine.from_model(model, cfg)


def _own_map(eng, key=("serving_step", None)):
    """The map of one of the engine's programs, compiled here again."""
    prog = eng._programs[key]
    return scopes.parse_phases(
        prog._ref().lower(*prog._args).compile().as_text())


def test_two_engines_each_read_their_own_program():
    """Two engines in one process (replicas behind a router) each own a
    `serving_step`: the second must not take the first one's place, and
    the first one's map must not go when the second does."""
    model_a, a = _engine(32, 2)
    model_b, b = _engine(48, 1)
    for eng in (a, b):                       # b registers last
        eng.add_request(list(range(1, 21)), max_new_tokens=3)
        eng.run_to_completion()
    want_a, want_b = _own_map(a), _own_map(b)
    assert want_a != want_b                  # else this test shows nothing
    assert a.phase_map() == want_a
    assert b.phase_map() == want_b
    same = [p for p in scopes.live_programs() if p.name == "serving_step"]
    assert {id(p) for p in same} >= {id(a._programs["serving_step", None]),
                                     id(b._programs["serving_step", None])}
    prog_b = weakref.ref(b._programs["serving_step", None])
    del b, model_b, eng, same
    gc.collect()
    assert prog_b() is None                  # went with its engine
    assert a.phase_map() == want_a


def test_spec_verify_maps_are_kept_by_token_length():
    from paddle_tpu.inference.speculative import NGramDrafter

    _model, eng = _engine(32, 2)
    eng.set_drafter(NGramDrafter(block_size=8), k=3)
    eng.add_request([4, 5, 4, 5, 4, 5, 4], max_new_tokens=8)
    eng.add_request([7, 8, 7, 8, 7, 8, 7, 8, 7], max_new_tokens=2)
    eng.run_to_completion()
    shapes = sorted(eng._spec_shapes)
    assert len(shapes) > 1, shapes           # one row, then two
    assert eng.phase_map("serving_spec_verify") is None     # which one?
    want = {n: _own_map(eng, ("serving_spec_verify", n)) for n in shapes}
    assert want[shapes[0]] != want[shapes[-1]]
    for tok_len in shapes:
        assert eng.phase_map("serving_spec_verify", tok_len) \
            == want[tok_len]
    assert eng.phase_map("serving_spec_verify", 1 << 20) is None


def test_merged_maps_keep_only_what_the_programs_agree_on():
    a = scopes.PhaseMap({"fusion.1": ("forward", "mlp"),
                         "fusion.2": ("forward", "attention"),
                         "copy.3": None}, inherited={"fusion.2"})
    b = scopes.PhaseMap({"fusion.1": ("forward", "mlp"),
                         "fusion.2": ("forward", "mlp"),
                         "fusion.9": ("forward", "head")},
                        inherited={"fusion.9"})
    merged = scopes.merge_phases([a, b])
    assert merged == {"fusion.1": ("forward", "mlp"), "fusion.2": None,
                      "copy.3": None, "fusion.9": ("forward", "head")}
    assert merged.inherited == {"fusion.9"}      # fusion.2 has no phase
    assert scopes.merge_phases([a]) == a


def test_samplers_are_named_and_scoped():
    from paddle_tpu.inference import serving

    logits = jnp.zeros((2, 16))
    text = serving._greedy_tokens_dev.lower(logits).compile().as_text()
    assert "jit_serving_sample_greedy" in text
    assert scopes.PREFIX + "sample" in text
    assert serving._sample_tokens_dev.__name__ == "serving_sample"
    assert serving._sample_topk_dev.__name__ == "serving_sample_topk"


def test_device_time_by_phase_joins_by_instruction_name():
    def fn(x):
        with scopes.scope("mlp"):
            return jnp.tanh(x @ x)

    jitted = jax.jit(fn)
    x = jnp.ones((8, 8))
    scopes.register_program("probe_join", jitted, scopes.abstract((x,)))
    phases = scopes.instruction_phases("probe_join")
    named = [k for k, v in phases.items() if v == ("forward", "mlp")]
    assert named
    events = [(f"%{named[0]} = f32[8,8]{{1,0}} fusion(...)", 0.0, 3.0),
              (named[0], 3.0, 4.0),                    # the CPU's bare name
              ("%not_in_the_program.7 = f32[] add(...)", 4.0, 5.0)]
    seconds, found, inherited = scopes.device_time_by_phase(
        events, "probe_join")
    assert seconds == {("forward", "mlp"): 4.0, scopes.UNATTRIBUTED: 1.0}
    assert found == pytest.approx(0.8)
    assert inherited == 0.0
    assert scopes.device_time_by_phase(events, "never_registered") is None
    # a map in place of a name, with the first event's phase only guessed
    guessed = scopes.PhaseMap(phases, inherited={named[0]})
    assert scopes.device_time_by_phase(events, guessed) == (
        seconds, pytest.approx(0.8), pytest.approx(0.8))


def _grad_of(fn, n):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                    argnums=tuple(range(n)))


def _kernel_cases():
    q = jnp.ones((1, 2, 128, 64), jnp.float32)
    seed = jnp.zeros((1,), jnp.int32)
    seg = jnp.zeros((1, 128), jnp.int32)
    x = jnp.ones((256, 128), jnp.float32)
    w = jnp.ones((128,), jnp.float32)

    def flash(a, b, c):
        return fa._flash_attention(a, b, c, None, seed, True, 0.0)

    def varlen(a, b, c):
        return va._varlen_attention(a, b, c, seg, seg, True)

    return {
        "flash_attention_fwd": (flash, (q, q, q)),
        "flash_attention_dkv": (_grad_of(flash, 3), (q, q, q)),
        "flash_attention_dq": (_grad_of(flash, 3), (q, q, q)),
        "varlen_attention_fwd": (varlen, (q, q, q)),
        "varlen_attention_dkv": (_grad_of(varlen, 3), (q, q, q)),
        "varlen_attention_dq": (_grad_of(varlen, 3), (q, q, q)),
        "rms_norm": (lambda a, b: rn.rms_norm(a, b, 1e-5), (x, w)),
        "rms_norm_noweight": (lambda a: rn.rms_norm(a, None, 1e-5), (x,)),
    }


def _pallas_calls(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"],
                        dict(eqn.params["metadata"] or {})))
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_calls(inner, out)
    return out


@pytest.mark.parametrize("kernel", [
    "flash_attention_fwd", "flash_attention_dkv", "flash_attention_dq",
    "varlen_attention_fwd", "varlen_attention_dkv", "varlen_attention_dq",
    "rms_norm", "rms_norm_noweight"])
def test_every_pallas_call_carries_its_name(monkeypatch, kernel):
    """Each of the 8 `pl.pallas_call` sites names its kernel, in `name`
    (the HLO instruction's and the lowered `kernel_name`) and in
    `metadata` (the compiled custom call's `kernel_metadata`); read from
    the equation's parameters, as tests/test_tpu_compile.py reads it from
    the program lowered for the chip."""
    monkeypatch.setenv("PT_USE_PALLAS", "1")
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    fn, args = _kernel_cases()[kernel]
    calls = _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr, [])
    assert (kernel, {"kernel": kernel}) in calls, calls
    assert all(name and meta == {"kernel": name} for name, meta in calls)


def test_model_view_prints_device_time_by_pass_and_block(tmp_path,
                                                         monkeypatch):
    monkeypatch.setenv("PT_PROFILE_DIR", str(tmp_path))
    tr = _trainer()
    tr.step(_ids(), _ids()).block_until_ready()       # compiled, registered
    prof = Profiler()
    prof.start()
    for _ in range(2):
        tr.step(_ids(), _ids()).block_until_ready()
    prof.stop()
    table = prof.summary(views=SummaryView.ModelView)
    head = [ln for ln in table.splitlines() if ln.startswith("train_step")]
    assert len(head) == 1, table
    for name in scopes.PASSES:
        assert re.search(rf"^  {name}\s+\d", table, re.M), (name, table)
    assert re.search(r"^    head_loss\s+\d", table, re.M), table
    # the default view is still the host-span table
    assert "trainer::step" in prof.summary()


def test_model_view_without_a_trace_says_so(tmp_path, monkeypatch):
    monkeypatch.setenv("PT_PROFILE_DIR", str(tmp_path))
    prof = Profiler(timer_only=True)
    prof.start()
    prof.stop()
    assert "no device trace" in prof.summary(views=[SummaryView.ModelView])
