"""Speculative decoding + fused decode step (ISSUE 13): pluggable
drafters verified k-at-a-time in ONE paged-attention step, bitwise
identity with the non-speculative engine (greedy AND sampled, across
disagg handoff and fleet drain), rejected-tail page rollback, retrace
churn bounded by pow2 row bucketing, and the single-region StableHLO
lowering of the decode iteration.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import transport as tr
from paddle_tpu.distributed.resilience import faults
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.inference import disagg
from paddle_tpu.inference.fleet_supervisor import (FleetSupervisor,
                                                   FleetSupervisorConfig)
from paddle_tpu.inference.router import Replica, ReplicaRouter
from paddle_tpu.inference.serving import (PagedCausalLM,
                                          PagedServingConfig,
                                          SamplingParams, ServingEngine)
from paddle_tpu.inference.speculative import (DraftModelDrafter, Drafter,
                                              NGramDrafter, from_env)
from paddle_tpu.profiler import metrics as _metrics


BASE = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
            num_kv_heads=2, ffn_size=64, block_size=8, num_blocks=48,
            max_batch=3, max_blocks_per_seq=6, token_budget=32)


def _cval(name):
    return _metrics.counter(name).value


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.disarm()


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    m = PagedCausalLM(PagedServingConfig(**BASE))
    m.eval()
    return m


def _fresh_engine(model, seed=0, **over):
    cfg = PagedServingConfig(**{**BASE, **over})
    cached = getattr(model, "_serving_shared", None)
    if cached is not None and cached[0] != (cfg.dtype, cfg.cache_quant,
                                            None):
        model._serving_shared = None
    return ServingEngine.from_model(model, cfg, seed=seed)


def _dense_greedy(model, prompt, n):
    ids = list(prompt)
    for _ in range(n):
        lg = model.forward_dense(
            paddle.to_tensor(np.asarray([ids], np.int64))).numpy()
        ids.append(int(np.argmax(lg[0, -1])))
    return ids[len(prompt):]


def _run(eng, prompts, max_new=8, sampling=None):
    rids = [eng.add_request(p, max_new_tokens=max_new, sampling=sampling)
            for p in prompts]
    out = eng.run_to_completion()
    return [out[r] for r in rids]


def _taught_ngram(model, prompts, max_new=8):
    """An NGramDrafter pre-fed the reference continuations, so verify
    steps have something worth accepting."""
    d = NGramDrafter(block_size=BASE["block_size"])
    for p in prompts:
        d.observe(list(p) + _dense_greedy(model, p, max_new))
    return d


# ---------------------------------------------------------------------------
# drafter units
# ---------------------------------------------------------------------------

def test_ngram_gram_backoff_and_unknown():
    d = NGramDrafter(n=3)
    d.observe([1, 2, 3, 4, 1, 2, 3, 5])
    # longest context wins: [2, 3] last led to 5 (most recent)
    assert d.propose([1, 2, 3], 1) == [5]
    # rolls forward through its own proposals, stops when the context
    # runs off the end of everything observed
    assert d.propose([4, 1, 2], 4) == [3, 5]
    # nothing known about this context at any order -> empty proposal
    assert d.propose([90, 91], 4) == []


def test_ngram_block_table_whole_block_proposals():
    bs = 4
    d = NGramDrafter(n=2, block_size=bs)
    stream = list(range(1, 13))              # 3 full blocks of 4
    d.observe(stream)
    # sitting exactly on the first block boundary: the digest chain of
    # block 0 is known, so the WHOLE next block comes back at once
    assert d.propose(stream[:4], bs) == stream[4:8]
    # two chained blocks -> third block
    assert d.propose(stream[:8], bs) == stream[8:12]
    # off-boundary falls back to gram proposals, never a wrong block
    assert d.propose(stream[:5], 2) == stream[5:7]


def test_draft_model_drafter_greedy_rollout(model):
    prompt = [5, 9, 3, 7, 1]
    d = DraftModelDrafter(model)
    assert d.propose(prompt, 3) == _dense_greedy(model, prompt, 3)
    # out-of-vocab context degrades to no proposal, not a crash
    assert d.propose([96, 200], 2) == []


# ---------------------------------------------------------------------------
# tentpole: bitwise identity, greedy and sampled
# ---------------------------------------------------------------------------

def test_spec_greedy_bitwise_identical(model):
    rng = np.random.RandomState(40)
    prompts = [list(rng.randint(1, 97, n)) for n in (9, 5, 12)]
    ref = _run(_fresh_engine(model), prompts)
    assert ref == [_dense_greedy(model, p, 8) for p in prompts]

    s0, a0 = _cval("serving/spec_steps"), _cval("serving/spec_accepted_tokens")
    eng = _fresh_engine(model)
    eng.set_drafter(_taught_ngram(model, prompts), k=4)
    assert _run(eng, prompts) == ref        # token-bitwise identical
    assert _cval("serving/spec_steps") > s0
    # the taught drafter actually drafted: >1 token per verify on avg
    assert _cval("serving/spec_accepted_tokens") > a0
    assert _metrics.gauge("serving/spec_accept_rate").value > 0.5
    assert _metrics.gauge("serving/spec_tokens_per_step").value > 1.0


def test_spec_sampled_bitwise_identical(model):
    """Acceptance compares against the salted SAMPLE at each position,
    so temperature/top-k/top-p streams are reproduced exactly too."""
    rng = np.random.RandomState(41)
    prompts = [list(rng.randint(1, 97, n)) for n in (7, 10)]
    sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.95)
    ref = _run(_fresh_engine(model, seed=6), prompts, sampling=sp)
    eng = _fresh_engine(model, seed=6)
    d = NGramDrafter(block_size=BASE["block_size"])
    for p, toks in zip(prompts, ref):
        d.observe(list(p) + toks)
    eng.set_drafter(d, k=4)
    assert _run(eng, prompts, sampling=sp) == ref


def test_spec_drafter_off_fallback(model):
    """A drafter with nothing to say degrades every verify step to a
    plain decode step — same stream, one token per step."""
    class Mute(Drafter):
        def propose(self, tokens, k):
            return []

    rng = np.random.RandomState(42)
    prompts = [list(rng.randint(1, 97, 8))]
    ref = _run(_fresh_engine(model), prompts)
    d0 = _cval("serving/spec_drafted_tokens")
    eng = _fresh_engine(model)
    eng.set_drafter(Mute(), k=4)
    assert _run(eng, prompts) == ref
    assert _cval("serving/spec_drafted_tokens") == d0


def test_spec_draft_model_drafter_end_to_end(model):
    """Self-draft (draft model == target) accepts everything greedily —
    the classic two-model scheme's best case, still bitwise-safe."""
    rng = np.random.RandomState(43)
    prompts = [list(rng.randint(1, 97, 6))]
    ref = _run(_fresh_engine(model), prompts)
    eng = _fresh_engine(model)
    eng.set_drafter(DraftModelDrafter(model), k=3)
    assert _run(eng, prompts) == ref
    assert _metrics.gauge("serving/spec_accept_rate").value == 1.0


def test_spec_mixed_batch_and_page_rollback(model):
    """Rows at different depths speculate together; rejected tails roll
    their KV pages back through the pool — nothing leaks."""
    rng = np.random.RandomState(44)
    prompts = [list(rng.randint(1, 97, n)) for n in (4, 15, 9)]
    ref = _run(_fresh_engine(model), prompts, max_new=10)
    eng = _fresh_engine(model)
    free0 = len(eng._free_pages)
    # adversarial drafter: plausible prefix then garbage, forcing
    # mid-proposal rejection (and page rollback) on most steps
    taught = _taught_ngram(model, prompts, max_new=10)

    class Tailed(Drafter):
        def propose(self, tokens, k):
            good = taught.propose(tokens, max(k - 2, 1))
            return (good + [1, 2])[:k]

        def observe(self, tokens, start=0):
            taught.observe(tokens, start=start)

    eng.set_drafter(Tailed(), k=4)
    assert _run(eng, prompts, max_new=10) == ref
    assert len(eng._free_pages) == free0          # every page came back


def test_set_drafter_validation(model):
    eng = _fresh_engine(model)
    with pytest.raises(ValueError):
        eng.set_drafter(NGramDrafter(), k=0)
    eng.set_drafter(NGramDrafter(), k=2)
    eng.set_drafter(None)                         # off again
    assert eng._drafter is None
    # artifact-loaded engines have no verify executable
    eng._compiled_verify = None
    with pytest.raises(ValueError):
        eng.set_drafter(NGramDrafter(), k=2)


def test_from_env_knobs(model, monkeypatch):
    eng = _fresh_engine(model)
    monkeypatch.setenv("PT_SPEC_DRAFTER", "off")
    assert from_env(eng) is None
    monkeypatch.setenv("PT_SPEC_DRAFTER", "ngram")
    monkeypatch.setenv("PT_SPEC_K", "3")
    d = from_env(eng)
    assert isinstance(d, NGramDrafter)
    assert d.block_size == BASE["block_size"]
    assert eng._spec_k == 3
    monkeypatch.setenv("PT_SPEC_DRAFTER", "bogus")
    with pytest.raises(ValueError):
        from_env(_fresh_engine(model))


# ---------------------------------------------------------------------------
# speculation composes with disagg handoff and fleet drain
# ---------------------------------------------------------------------------

@pytest.fixture
def pair():
    store = TCPStore("127.0.0.1", 0, is_master=True)
    t0 = tr.TensorTransport(0, 2, store, bind_host="127.0.0.1",
                            timeout=15.0, ack_timeout=3.0)
    t1 = tr.TensorTransport(1, 2, store, bind_host="127.0.0.1",
                            timeout=15.0, ack_timeout=3.0)
    yield t0, t1
    faults.disarm()
    t0.close()
    t1.close()
    store.close()


def test_spec_disagg_handoff_bitwise_identical(model, pair):
    """A speculating decode worker behind the prefill->decode transport
    produces the same stream as one plain engine — migrated requests
    land at their decode tip and verify steps pick up from there."""
    t0, t1 = pair
    rng = np.random.RandomState(45)
    prompts = [list(rng.randint(1, 97, n)) for n in (9, 14)]
    sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.9)
    ref = _run(_fresh_engine(model, seed=5), prompts, max_new=6,
               sampling=sp)

    pre = _fresh_engine(model, seed=5)
    dec = _fresh_engine(model, seed=5)
    d = NGramDrafter(block_size=BASE["block_size"])
    for p, toks in zip(prompts, ref):
        d.observe(list(p) + toks)
    dec.set_drafter(d, k=4)
    pw = disagg.PrefillWorker(pre, t0, decode_rank=1)
    dw = disagg.DecodeWorker(dec, t1, prefill_rank=0)
    for p in prompts:
        pw.submit(p, max_new_tokens=6, sampling=sp)
    assert len(pw.pump()) == len(prompts)
    local = dw.accept(len(prompts))
    s0 = _cval("serving/spec_steps")
    res = dw.run(window=4)
    assert [res[r] for r in local] == ref
    assert _cval("serving/spec_steps") > s0       # it DID speculate


def test_spec_stream_survives_fleet_drain_bitwise(model):
    """kill@decode on a speculating replica: live spec requests drain
    to the peer (also speculating) and the delivered streams stay
    token-bitwise identical to the unfaulted fleet AND to the
    non-speculative fleet."""
    prompt_lens = (9, 11, 7, 13)
    rng = np.random.RandomState(31)
    prompts = [list(rng.randint(1, 90, n)) for n in prompt_lens]
    sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.95)

    def build(spec):
        def factory(idx):
            eng = _fresh_engine(model, seed=10 + idx)
            eng.fault_rank = idx
            if spec:
                d = NGramDrafter(block_size=BASE["block_size"])
                for p in prompts:
                    d.observe(list(p) + _dense_greedy(model, p, 6))
                eng.set_drafter(d, k=4)
            return eng

        router = ReplicaRouter([Replica(factory(i), name=f"r{i}",
                                        restore_after=2)
                                for i in range(2)])
        sup = FleetSupervisor(router, engine_factory=factory,
                              cfg=FleetSupervisorConfig(backoff_base_s=0.0))
        return router, sup

    def run(router):
        hs = [router.submit(list(p), max_new_tokens=6, sampling=sp)
              for p in prompts]
        out = router.run_to_completion()
        return [out[h] for h in hs]

    plain = run(build(spec=False)[0])
    unfaulted = run(build(spec=True)[0])
    assert unfaulted == plain                     # spec never drifts

    fail0 = _cval("serving/replica_failures")
    faults.arm("kill@decode#2:rank=1")
    router, sup = build(spec=True)
    got = run(router)
    faults.disarm()
    assert got == plain                           # across the drain too
    assert sup.restarts == [0, 1]
    assert sup.drained_handles
    assert _cval("serving/replica_failures") >= fail0 + 1
    assert router.timed_out() == []


# ---------------------------------------------------------------------------
# satellite: decode-window retrace churn is bounded by pow2 bucketing
# ---------------------------------------------------------------------------

def test_decode_window_retrace_bounded_by_bucketing(model):
    """Drifting decode batch sizes (4 rows, then 3 as requests finish,
    then a 3-row wave) bucket onto the same pow2 row count: ONE window
    trace, ZERO decode_window retraces."""
    rng = np.random.RandomState(46)
    eng = _fresh_engine(model, max_batch=4)
    r0 = _cval("jit/retrace_cause/decode_window")

    def drain(n_prompts, max_new):
        for i in range(n_prompts):
            eng.add_request(list(rng.randint(1, 97, 6 + i)),
                            max_new_tokens=max_new)
        while any(r.length - r.cached > 1 for r in eng.pending()):
            eng.step()                            # prefill to the tip
        while eng.pending():
            assert eng.decode_run(4)

    drain(4, max_new=8)       # full batch; tail windows shrink 4->2->1
    n_fns = len(eng._window_fns)
    assert n_fns <= 3         # at most log2 window lengths per bucket
    r_mid = _cval("jit/retrace_cause/decode_window")
    drain(3, max_new=8)       # 3 rows -> bucketed up to 4: full reuse
    assert len(eng._window_fns) == n_fns
    assert _cval("jit/retrace_cause/decode_window") == r_mid
    # ...and a genuinely new row bucket IS counted, with its cause
    drain(2, max_new=8)
    assert len(eng._window_fns) > n_fns
    assert _cval("jit/retrace_cause/decode_window") > r_mid
    assert _cval("jit/retrace_count") > r0


def test_spec_verify_shapes_bucketed(model):
    """Verify tok_lens are pow2-bucketed: k=3 drafts across 3 rows pack
    into a handful of shapes, each counted once."""
    rng = np.random.RandomState(47)
    prompts = [list(rng.randint(1, 97, n)) for n in (9, 5, 12)]
    eng = _fresh_engine(model)
    eng.set_drafter(_taught_ngram(model, prompts), k=3)
    _run(eng, prompts)
    assert eng._spec_shapes                        # it compiled verify
    assert all(t & (t - 1) == 0 or t == BASE["token_budget"]
               for t in eng._spec_shapes)          # pow2 (or budget cap)
    assert len(eng._spec_shapes) <= 4


# ---------------------------------------------------------------------------
# satellite: single-region fused decode lowering
# ---------------------------------------------------------------------------

def test_lower_fused_decode_single_module(model):
    f0 = _cval("compiler/fused_decode_regions")
    eng = _fresh_engine(model)
    text = eng.lower_fused_decode(n_rows=2)
    assert "module" in text and "func.func" in text
    assert text.count("func.func public @main") == 1   # ONE region
    # the decode body actually lowered: paged gather + attention matmuls
    assert "stablehlo.dot" in text or "stablehlo.dot_general" in text
    assert _cval("compiler/fused_decode_regions") == f0 + 1


def test_fusereport_decode_preset(tmp_path):
    """tools/fusereport.py --preset decode: verified auto_fuse over the
    captured decode iteration, with roofline + .mlir artifacts."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    try:
        import fusereport
    finally:
        sys.path.pop(0)
    rep = fusereport.build_report("decode", stablehlo_dir=str(tmp_path))
    assert rep["verified"]
    assert rep["regions"]                          # fused something
    assert rep["post"]["ops"] < rep["pre"]["ops"]
    assert rep["bytes_moved_saved"] > 0
    assert any(p.endswith(".module.mlir")
               for p in rep["stablehlo_artifacts"])
