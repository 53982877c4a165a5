"""Live weight publishing (ISSUE 15): versioned double-buffered hot
swap with per-request version pinning, CRC'd transport shipping, canary
gating over golden prompts, store-fenced rollout epochs, bitwise
rollback, prefix-cache version isolation, and the speculative-drafter
hand-off across a swap — chaos-tested at the ``publish`` fault site.
"""
import json

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.distributed.resilience import faults
from paddle_tpu.distributed.resilience.errors import (
    PublishRejectedError, WeightTransferError)
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.inference import disagg
from paddle_tpu.inference.fleet_supervisor import (FleetSupervisor,
                                                   FleetSupervisorConfig,
                                                   LoopbackTransport)
from paddle_tpu.inference.prefix_cache import PrefixCache
from paddle_tpu.inference.router import Replica, ReplicaRouter
from paddle_tpu.inference.serving import (PagedCausalLM,
                                          PagedServingConfig,
                                          SamplingParams, ServingEngine)
from paddle_tpu.inference.weight_publish import (PublishPolicy,
                                                 WeightPublisher,
                                                 build_weight_set,
                                                 receive_weight_set,
                                                 send_weight_set)
from paddle_tpu.jit import functional as FB
from paddle_tpu.profiler import metrics as _metrics

BASE = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
            num_kv_heads=2, ffn_size=64, block_size=8, num_blocks=48,
            max_batch=3, max_blocks_per_seq=6, token_budget=32)

SP = SamplingParams(temperature=0.7, top_k=12, top_p=0.9)


def _cval(name):
    return _metrics.counter(name).value


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    m = PagedCausalLM(PagedServingConfig(**BASE))
    m.eval()
    return m


def _fresh_engine(model, seed=0, **over):
    ws = over.pop("_weight_stream", None)
    cfg = PagedServingConfig(**{**BASE, **over})
    cached = getattr(model, "_serving_shared", None)
    if cached is not None and cached[0] != (cfg.dtype, cfg.cache_quant,
                                            ws):
        model._serving_shared = None
    return ServingEngine.from_model(model, cfg, seed=seed,
                                    weight_stream=ws)


def _perturbed(model, scale=0.05, seed=5):
    """A genuinely different (finite, canary-passing) candidate param
    tree: each floating tensor plus noise at a few percent of its own
    spread."""
    rng = np.random.RandomState(seed)
    out = {}
    for k, v in FB.current_params(model).items():
        a = np.asarray(jax.device_get(v))
        if np.issubdtype(a.dtype, np.floating):
            f = a.astype(np.float32)
            out[k] = (f + rng.normal(0.0, scale * (np.std(f) + 1e-6),
                                     f.shape)).astype(a.dtype)
        else:
            out[k] = a
    return out


def _publish_direct(engine, model, params, version, ws=None):
    """Stage + commit one version on one engine, bypassing the
    publisher (engine-contract tests)."""
    arrays, crcs = build_weight_set(model, params, engine.cfg,
                                    weight_stream=ws)
    engine.stage_weight_set(version, arrays, crcs=crcs)
    engine.commit_weight_set(version)


def _drain(engine):
    for _ in range(600):
        if not engine.pending():
            break
        engine.step()
    return {rid: list(r.generated)
            for rid, r in engine._requests.items()}


def _regen(model, prompt, salt_rid, salt_seed, max_new, version=0,
           params=None, ws=None, sampling=SP):
    """Bitwise referee: regenerate one stream on a FRESH single engine
    holding only its pinned version, under the recorded salt identity."""
    eng = _fresh_engine(model, seed=123, _weight_stream=ws)
    if version > 0:
        _publish_direct(eng, model, params, version, ws=ws)
    rid = eng.add_request(list(prompt), max_new_tokens=max_new,
                          sampling=sampling)
    r = eng._requests[rid]
    r.salt_rid, r.salt_seed = salt_rid, salt_seed
    while not r.done:
        eng.step()
    return list(r.generated)


# ---------------------------------------------------------------------------
# engine contract: stage / commit / swap / rollback
# ---------------------------------------------------------------------------

def test_stage_commit_swap_contract(model):
    eng = _fresh_engine(model, seed=1)
    new = _perturbed(model)
    arrays, crcs = build_weight_set(model, new, eng.cfg)
    assert eng.active_weight_version == 0
    eng.stage_weight_set(1, arrays, crcs=crcs)
    # staged is NOT servable: nothing pins to it, requeues skip it
    assert not eng.has_weight_version(1)
    old = eng.commit_weight_set(1)
    assert old == 0 and eng.active_weight_version == 1
    # the previous set is retained for pinned streams and rollback
    assert eng.has_weight_version(0) and eng.has_weight_version(1)
    assert _metrics.gauge("serving/weight_version").value == 1
    # new admissions pin to the active version
    rid = eng.add_request([5, 6, 7], max_new_tokens=2, sampling=SP)
    assert eng._requests[rid].weight_version == 1
    _drain(eng)
    # stale and never-staged commits are refused as policy, not crash
    with pytest.raises(PublishRejectedError) as ei:
        eng.commit_weight_set(1)
    assert ei.value.reason == "stale_version"
    with pytest.raises(PublishRejectedError) as ei:
        eng.commit_weight_set(7)
    assert ei.value.reason == "not_staged"


def test_stage_rejects_torn_and_mismatched_sets(model):
    eng = _fresh_engine(model, seed=1)
    new = _perturbed(model)
    arrays, crcs = build_weight_set(model, new, eng.cfg)
    # wrong tensor count
    with pytest.raises(WeightTransferError):
        eng.stage_weight_set(2, arrays[:-1])
    # CRC mismatch (a torn byte between builder and buffer)
    bad = [a.copy() for a in arrays]
    big = max(range(len(bad)), key=lambda i: bad[i].nbytes)
    buf = bytearray(bad[big].tobytes())
    buf[len(buf) // 2] ^= 0xFF
    bad[big] = np.frombuffer(bytes(buf), bad[big].dtype).reshape(
        bad[big].shape)
    with pytest.raises(WeightTransferError):
        eng.stage_weight_set(2, bad, crcs=crcs)
    # nothing half-staged survives a refused transfer
    assert 2 not in eng._staged_weights
    assert eng.active_weight_version == 0


def test_pinned_version_streams_bitwise_across_swap(model):
    """The tentpole identity: a stream admitted under N finishes under
    N even when N+1 lands mid-flight, and both cohorts match fresh
    single-version regenerations token-for-token."""
    new = _perturbed(model)
    eng = _fresh_engine(model, seed=7)
    prompt_a, prompt_b = [5, 6, 7, 8], [9, 10, 11]
    rid_a = eng.add_request(prompt_a, max_new_tokens=6, sampling=SP)
    eng.step()                                  # A genuinely in flight
    _publish_direct(eng, model, new, 1)
    rid_b = eng.add_request(prompt_b, max_new_tokens=6, sampling=SP)
    ra, rb = eng._requests[rid_a], eng._requests[rid_b]
    assert ra.weight_version == 0 and rb.weight_version == 1
    out = _drain(eng)
    assert out[rid_a] == _regen(model, prompt_a, ra.salt_rid, 7, 6)
    assert out[rid_b] == _regen(model, prompt_b, rb.salt_rid, 7, 6,
                                version=1, params=new)
    # the two versions genuinely disagree on at least one of the
    # prompts (otherwise this test proves nothing)
    assert out[rid_a] != _regen(model, prompt_a, ra.salt_rid, 7, 6,
                                version=1, params=new) \
        or out[rid_b] != _regen(model, prompt_b, rb.salt_rid, 7, 6)


def test_scheduler_never_mixes_versions_in_one_step(model):
    eng = _fresh_engine(model, seed=2)
    new = _perturbed(model)
    rids0 = [eng.add_request([3 + i, 4, 5], max_new_tokens=4,
                             sampling=SP) for i in range(2)]
    eng.step()
    _publish_direct(eng, model, new, 1)
    rids1 = [eng.add_request([20 + i, 21], max_new_tokens=4,
                             sampling=SP) for i in range(2)]
    orig_sched = eng._schedule

    def checked():
        rows = orig_sched()
        vs = {r.weight_version for r, _ in rows}
        assert len(vs) <= 1, f"mixed versions in one step: {vs}"
        return rows

    eng._schedule = checked
    out = _drain(eng)
    assert all(len(out[r]) == 4 for r in rids0 + rids1)


def test_rollback_bitwise_and_inflight_reset(model):
    """Post-promote anomaly: rollback re-binds the retained buffer and
    RESETS streams pinned to the bad version — their regeneration
    equals a run where the promote never happened."""
    new = _perturbed(model)
    eng = _fresh_engine(model, seed=9)
    rb0 = _cval("serving/weight_rollbacks")
    _publish_direct(eng, model, new, 1)
    prompt = [4, 5, 6, 7]
    rid = eng.add_request(prompt, max_new_tokens=6, sampling=SP)
    eng.step()
    eng.step()
    r = eng._requests[rid]
    # one token settled, one in flight: the rollback settles it first
    assert r.weight_version == 1 and len(r.generated) == 1 and r.ahead == 1
    prev = eng.rollback_weight_set()
    assert eng._flight is None
    assert prev == 0 and eng.active_weight_version == 0
    assert r.weight_version == 0 and r.generated == [] and r.cached == 0
    out = _drain(eng)
    assert out[rid] == _regen(model, prompt, r.salt_rid, 9, 6)
    assert _cval("serving/weight_rollbacks") == rb0 + 1
    # a rollback cannot be rolled back
    with pytest.raises(PublishRejectedError) as ei:
        eng.rollback_weight_set()
    assert ei.value.reason == "no_previous"


def test_probe_logits_is_stateless_and_scores_staged(model):
    eng = _fresh_engine(model, seed=4)
    new = _perturbed(model)
    free0 = len(eng._free_pages)
    base = eng.probe_logits([5, 6, 7])
    assert base.shape == (BASE["vocab_size"],)
    arrays, crcs = build_weight_set(model, new, eng.cfg)
    eng.stage_weight_set(1, arrays, crcs=crcs)
    staged = eng.probe_logits([5, 6, 7], version=1)
    # the staged probe scored the CANDIDATE, not the active set
    assert not np.allclose(base, staged)
    # and committing makes the staged scores the active ones
    eng.commit_weight_set(1)
    after = eng.probe_logits([5, 6, 7])
    np.testing.assert_array_equal(staged, after)
    # stateless: no request admitted, no page taken
    assert len(eng._free_pages) == free0 and not eng.pending()


# ---------------------------------------------------------------------------
# transport shipping
# ---------------------------------------------------------------------------

def test_weight_set_ships_over_transport_with_crcs(model):
    eng = _fresh_engine(model, seed=3)
    new = _perturbed(model)
    arrays, crcs = build_weight_set(model, new, eng.cfg)
    tp = LoopbackTransport()
    n = send_weight_set(tp, 0, 1, arrays, crcs)
    assert n == sum(a.nbytes for a in arrays)
    assert receive_weight_set(eng, tp, 0) == 1
    eng.commit_weight_set(1)
    # byte-exact arrival: the staged-then-committed flat list matches
    # the built payload tensor-for-tensor
    for got, sent in zip(eng._params, arrays):
        np.testing.assert_array_equal(np.asarray(jax.device_get(got)),
                                      sent)


# ---------------------------------------------------------------------------
# prefix cache version isolation
# ---------------------------------------------------------------------------

def test_prefix_cache_version_isolation_unit():
    cache = PrefixCache(block_size=4)
    tokens = list(range(1, 14))                  # 3 full blocks + tip
    k0 = cache.insert(tokens, [1, 2, 3], version=0)
    pages, held, n = cache.match(tokens, version=0)
    assert pages == [1, 2, 3] and n == 12
    cache.release(held)
    # KV produced under version 0 never matches a version-1 request
    pages, held, n = cache.match(tokens, version=1)
    assert pages == [] and held == [] and n == 0
    # the SAME prompt under version 1 lives on a disjoint trie path
    k1 = cache.insert(tokens, [4, 5, 6], version=1)
    p0, h0, _ = cache.match(tokens, version=0)
    p1, h1, _ = cache.match(tokens, version=1)
    assert p0 == [1, 2, 3] and p1 == [4, 5, 6]
    for held in (h0, h1, k0, k1):
        cache.release(held)


def test_engine_prefix_reuse_stays_within_version(model):
    eng = _fresh_engine(model, seed=6, prefix_cache=True)
    new = _perturbed(model)
    prompt = list(range(1, 17))                 # two full blocks
    rid0 = eng.add_request(prompt + [40], max_new_tokens=2, sampling=SP)
    _drain(eng)
    # same-version resubmission reuses the registered prefix pages
    rid1 = eng.add_request(prompt + [41], max_new_tokens=2, sampling=SP)
    assert eng._requests[rid1].cached > 0
    _drain(eng)
    _publish_direct(eng, model, new, 1)
    # the v0 KV is poison for a v1 stream: no match across the swap
    rid2 = eng.add_request(prompt + [42], max_new_tokens=2, sampling=SP)
    assert eng._requests[rid2].weight_version == 1
    assert eng._requests[rid2].cached == 0
    _drain(eng)


# ---------------------------------------------------------------------------
# requeue / migrate hand-offs carry the pin
# ---------------------------------------------------------------------------

def test_requeue_resumes_under_origin_version(model):
    """A deadline-evicted request requeued onto a peer resumes under
    the version its stream STARTED on — the peer serves it from its
    retained buffer even though its active version moved on."""
    import time as _t

    new = _perturbed(model)
    e0 = _fresh_engine(model, seed=11)
    e1 = _fresh_engine(model, seed=12)
    router = ReplicaRouter([Replica(e0, "a"), Replica(e1, "b")])
    # both replicas promote to v1; v0 stays retained (rollback buffer)
    for e in (e0, e1):
        _publish_direct(e, model, new, 1)
    # a v0-pinned stream exists only if admitted pre-swap: fake the
    # clock back by admitting, then re-pinning to the retained version
    h = router.submit([7, 8, 9, 10], max_new_tokens=3, sampling=SP,
                      deadline_s=0.0)
    idx, rid = router._handles[h]
    eng = router.replicas[idx].engine
    eng.pin_weight_version(rid, 0)
    r = eng._requests[rid]
    assert r.weight_version == 0
    _t.sleep(0.01)
    out = router.run_to_completion()
    n_idx, n_rid = router._handles[h]
    assert n_idx != idx                          # followed the requeue
    nr = router.replicas[n_idx].engine._requests[n_rid]
    assert nr.weight_version == 0                # pin survived
    assert out[h] == _regen(model, [7, 8, 9, 10], nr.salt_rid,
                            router.replicas[idx].engine.seed, 3)


def test_requeue_skips_replica_without_version(model):
    """A replica that cannot serve the pinned version is skipped by the
    requeue hook rather than silently decoding under wrong weights."""
    import time as _t

    new = _perturbed(model)
    e0 = _fresh_engine(model, seed=13)
    e1 = _fresh_engine(model, seed=14)
    router = ReplicaRouter([Replica(e0, "a"), Replica(e1, "b")])
    # e1 serves ONLY v1 (retained v0 dropped: nothing pins to it there)
    _publish_direct(e1, model, new, 1)
    e1._weight_sets.pop(0, None)
    e1._prev_wv = None
    h = router.submit([3, 4, 5], max_new_tokens=2, sampling=SP,
                      deadline_s=0.0, prefer=0)
    idx, rid = router._handles[h]
    assert idx == 0
    _t.sleep(0.01)
    router.run_to_completion()
    # nowhere to retry: e1 was skipped, the handle reports the timeout
    assert router._handles[h] == (idx, rid)
    assert h in router.timed_out()


def test_migrate_carries_pin_and_refuses_wrong_version(model):
    new = _perturbed(model)
    src = _fresh_engine(model, seed=15)
    _publish_direct(src, model, new, 1)
    rid = src.add_request([6, 7, 8, 9], max_new_tokens=4, sampling=SP)
    while not (src._requests[rid].generated
               and src._requests[rid].length - src._requests[rid].cached
               == 1):
        src.step()
    # destination that serves v1: hand-off resumes under the pin
    dst = _fresh_engine(model, seed=16)
    _publish_direct(dst, model, new, 1)
    tp = LoopbackTransport()
    disagg.migrate_request(src, rid, tp, dst=0)
    new_rid = disagg.receive_request(dst, tp, src=0)
    assert dst._requests[new_rid].weight_version == 1
    # destination still on v0: the hand-off fails LOUDLY
    src2 = _fresh_engine(model, seed=17)
    _publish_direct(src2, model, new, 1)
    rid2 = src2.add_request([6, 7, 8], max_new_tokens=3, sampling=SP)
    while not (src2._requests[rid2].generated
               and src2._requests[rid2].length
               - src2._requests[rid2].cached == 1):
        src2.step()
    cold = _fresh_engine(model, seed=18)
    tp2 = LoopbackTransport()
    disagg.migrate_request(src2, rid2, tp2, dst=0)
    free0 = len(cold._free_pages)
    with pytest.raises(ValueError, match="weight version"):
        disagg.receive_request(cold, tp2, src=0)
    assert len(cold._free_pages) == free0        # pages released


# ---------------------------------------------------------------------------
# publisher: canary gate, fence, fleet rollout
# ---------------------------------------------------------------------------

def _mk_fleet(model, n=3, ws=None, store=None, supervisor=False,
              policy=None):
    def factory(idx):
        return _fresh_engine(model, seed=30 + idx, _weight_stream=ws)

    engines = [factory(i) for i in range(n)]
    for i, e in enumerate(engines):
        e.fault_rank = i
    router = ReplicaRouter(
        [Replica(e, name=f"r{i}") for i, e in enumerate(engines)])
    sup = None
    if supervisor:
        sup = FleetSupervisor(router, engine_factory=factory,
                              cfg=FleetSupervisorConfig(
                                  backoff_base_s=0.001))
    pub = WeightPublisher(router, model, store=store, supervisor=sup,
                          policy=policy)
    return engines, router, sup, pub


def test_publish_promotes_fleet_and_reports(model):
    engines, router, _, pub = _mk_fleet(model, n=3)
    p0 = _cval("serving/weight_publishes")
    rep = pub.publish(params=_perturbed(model))
    assert rep.version == 1 and rep.missed == []
    assert len(rep.committed) == 3 and rep.canary == "r0"
    assert all(e.active_weight_version == 1 for e in engines)
    assert pub.version == 1
    assert _cval("serving/weight_publishes") == p0 + 1
    # stale re-publish of a consumed epoch is refused
    with pytest.raises(PublishRejectedError) as ei:
        pub.publish(params=_perturbed(model), version=1)
    assert ei.value.reason == "stale_version"


def test_canary_rejects_nonfinite_before_any_token(model):
    engines, router, _, pub = _mk_fleet(model, n=2)
    cf0 = _cval("serving/canary_failures")
    bad = _perturbed(model)
    k = next(k for k, v in bad.items()
             if np.issubdtype(v.dtype, np.floating))
    poisoned = bad[k].astype(np.float32)
    poisoned.flat[::7] = np.nan
    bad[k] = poisoned.astype(bad[k].dtype)
    with pytest.raises(PublishRejectedError) as ei:
        pub.publish(params=bad)
    assert ei.value.reason == "canary_nonfinite"
    assert _cval("serving/canary_failures") == cf0 + 1
    # the poisoned version never became active OR staged anywhere
    for e in engines:
        assert e.active_weight_version == 0
        assert e._staged_weights == {}
    # the refused epoch is consumed; the next publish advances past it
    rep = pub.publish(params=_perturbed(model))
    assert rep.version == 2


def test_canary_rejects_drifted_distribution(model):
    engines, router, _, pub = _mk_fleet(model, n=2)
    # a finite but wildly different candidate: freshly re-randomized
    # weights scaled up — the active version's greedy continuation
    # becomes very unlikely under it
    rng = np.random.RandomState(99)
    bad = {}
    for k, v in FB.current_params(model).items():
        a = np.asarray(jax.device_get(v))
        if np.issubdtype(a.dtype, np.floating):
            bad[k] = (rng.standard_normal(a.shape) * 8.0).astype(a.dtype)
        else:
            bad[k] = a
    with pytest.raises(PublishRejectedError) as ei:
        pub.publish(params=bad)
    assert ei.value.reason == "canary_drift"
    assert all(e.active_weight_version == 0 for e in engines)


def test_fenced_epoch_rejects_second_controller(model):
    store = TCPStore("127.0.0.1", 0, is_master=True)
    try:
        _, router, _, pub_a = _mk_fleet(model, n=2, store=store)
        rep = pub_a.publish(params=_perturbed(model))
        assert rep.version == 1
        man = json.loads(bytes(store.get_nowait(
            "publish/weights/manifest")).decode())
        assert man["version"] == 1 and man["state"] == "committed"
        # a second controller over the same store adopts the epoch
        # counter and cannot re-claim a consumed epoch
        pub_b = WeightPublisher(router, model, store=store)
        assert pub_b._next == 2
        with pytest.raises(PublishRejectedError) as ei:
            pub_b.publish(params=_perturbed(model), version=1)
        assert ei.value.reason == "stale_version"
        assert ei.value.fence_version == 1
        # the fresh epoch goes through
        rep2 = pub_b.publish(params=_perturbed(model, seed=8))
        assert rep2.version == 2
    finally:
        store.close()


def test_publisher_rollback_fleet_bitwise(model):
    engines, router, _, pub = _mk_fleet(model, n=2)
    new = _perturbed(model)
    pub.publish(params=new)
    h = router.submit([5, 6, 7, 8], max_new_tokens=4, sampling=SP)
    for _ in range(2):
        router.step_all()
    prev = pub.rollback(reason="anomaly-test")
    assert prev == 0 and pub.version == 0
    assert all(e.active_weight_version == 0 for e in engines)
    out = router.run_to_completion()
    idx, rid = router._handles[h]
    eng = router.replicas[idx].engine
    r = eng._requests[rid]
    assert r.weight_version == 0
    # bitwise-equal to never having promoted
    assert out[h] == _regen(model, [5, 6, 7, 8], r.salt_rid, eng.seed, 4)
    # the rolled-back epoch is consumed: the next publish outruns it
    rep = pub.publish(params=_perturbed(model, seed=6))
    assert rep.version == 2


# ---------------------------------------------------------------------------
# chaos: the publish fault site
# ---------------------------------------------------------------------------

def test_faultplan_knows_publish_site():
    plan = faults.parse_plan(
        "kill@publish:rank=1;delay@publish:ms=1;"
        "drop@publish:rank=0;corrupt@publish")
    assert {r.site for r in plan.rules} == {"publish"}
    assert {r.kind for r in plan.rules} == {"kill", "delay", "drop",
                                            "corrupt"}
    with pytest.raises(ValueError, match="publish"):
        faults.parse_plan("dup@publish")


def test_kill_at_publish_leaves_n_intact_then_catchup(model):
    """The ISSUE torn-update clause: kill@publish mid-transfer fells
    the replica with version N fully intact; the supervisor restart
    path replays the committed version before it takes traffic."""
    engines, router, sup, pub = _mk_fleet(model, n=3, supervisor=True)
    cu0 = _cval("serving/publish_catchups")
    try:
        faults.arm("kill@publish:rank=2")
        rep = pub.publish(params=_perturbed(model))
    finally:
        faults.disarm()
    assert rep.version == 1
    assert "r2" in rep.missed and len(rep.committed) == 2
    assert engines[2].dead                       # felled mid-stage
    assert engines[2]._staged_weights == {}      # nothing half-staged
    assert engines[2].active_weight_version == 0  # N intact
    # supervisor recovery: restart + weight_catchup converge the fleet
    sup.pump()
    fresh = router.replicas[2].engine
    assert not fresh.dead
    assert fresh.active_weight_version == 1
    assert _cval("serving/publish_catchups") == cu0 + 1
    assert all(rep2.engine.active_weight_version == 1
               for rep2 in router.replicas)


def test_drop_and_corrupt_at_publish_then_reconcile(model):
    engines, router, _, pub = _mk_fleet(model, n=3)
    miss0 = _cval("serving/publish_missed")
    try:
        faults.arm("drop@publish:rank=1")
        rep = pub.publish(params=_perturbed(model))
    finally:
        faults.disarm()
    assert "r1" in rep.missed
    assert not engines[1].dead                   # alive, just behind
    assert engines[1].active_weight_version == 0
    assert _cval("serving/publish_missed") == miss0 + 1
    # corrupt on the next rollout: the CRC re-verify refuses the set
    try:
        faults.arm("corrupt@publish:rank=2")
        rep2 = pub.publish(params=_perturbed(model, seed=8))
    finally:
        faults.disarm()
    assert "r2" in rep2.missed
    assert engines[2].active_weight_version in (0, 1)  # old set intact
    assert engines[2]._staged_weights == {}
    # the v2 rollout already carried the v1 straggler forward — an
    # alive-but-behind replica is promoted by the NEXT publish
    assert "r1" in rep2.committed
    assert engines[1].active_weight_version == rep2.version
    # reconcile converges the remaining straggler onto the epoch
    updated = pub.reconcile()
    assert updated == ["r2"]
    assert all(e.active_weight_version == rep2.version for e in engines)


# ---------------------------------------------------------------------------
# satellite 3: trainer-mesh -> serving reshard round trip, quantized
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ws", ["int8", "int4"])
def test_checkpoint_reshard_roundtrip_quantized_parity(model, ws,
                                                       tmp_path):
    """A trainer checkpoint saved under a sharded mesh, published into
    a weight-streaming fleet, must serve the SAME tokens as an engine
    built directly over those params with the same quantization — the
    publish pipeline (reshard-on-load -> cast -> int8/int4 quantize ->
    flatten) replicates ``from_model`` bitwise."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed.checkpoint import save_state_dict

    new = _perturbed(model, seed=21)
    # save the candidate as a TRAINER-mesh checkpoint: every 2d tensor
    # sharded over a 4-way axis (serving loads it replicated)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("x",))
    sd = {}
    for k, v in new.items():
        if v.ndim >= 1 and v.shape[0] % 4 == 0 \
                and np.issubdtype(v.dtype, np.floating):
            spec = P(*(["x"] + [None] * (v.ndim - 1)))
            sd[k] = paddle.to_tensor(
                jax.device_put(v, NamedSharding(mesh, spec)))
        else:
            sd[k] = paddle.to_tensor(v)
    save_state_dict(sd, str(tmp_path / "ckpt"))

    engines, router, _, pub = _mk_fleet(model, n=2, ws=ws)
    rep = pub.publish_from_checkpoint(str(tmp_path / "ckpt"))
    assert rep.version == 1 and rep.missed == []

    prompt = [5, 6, 7, 8, 9]
    h = router.submit(prompt, max_new_tokens=5, sampling=SP)
    out = router.run_to_completion()
    idx, rid = router._handles[h]
    eng = router.replicas[idx].engine
    r = eng._requests[rid]
    assert r.weight_version == 1
    # referee: a second model instance carrying the candidate params,
    # quantized by from_model itself (not the publisher)
    paddle.seed(3)
    m2 = PagedCausalLM(PagedServingConfig(**BASE))
    m2.eval()
    FB.write_back(m2, {k: np.asarray(v) for k, v in new.items()})
    assert out[h] == _regen(m2, prompt, r.salt_rid, eng.seed, 5, ws=ws)


# ---------------------------------------------------------------------------
# satellite 2: speculative drafter across the swap
# ---------------------------------------------------------------------------

def test_drafter_republish_and_fallback(model):
    from paddle_tpu.inference.speculative import (DraftModelDrafter,
                                                  NGramDrafter)

    new = _perturbed(model, seed=31)
    draft_new = _perturbed(model, seed=32)
    rp0 = _cval("serving/spec_drafter_republished")
    fb0 = _cval("serving/spec_drafter_fallbacks")

    # republish path: draft weights ship alongside the target set
    paddle.seed(4)
    draft = PagedCausalLM(PagedServingConfig(**BASE))
    draft.eval()
    engines, router, _, pub = _mk_fleet(model, n=1)
    engines[0].set_drafter(DraftModelDrafter(draft), k=3)
    pub.publish(params=new, draft_params=draft_new)
    d = engines[0]._drafter
    assert isinstance(d, DraftModelDrafter)
    got = {k: np.asarray(jax.device_get(v))
           for k, v in FB.current_params(draft).items()}
    k0 = next(iter(draft_new))
    np.testing.assert_array_equal(got[k0], np.asarray(draft_new[k0]))
    assert _cval("serving/spec_drafter_republished") == rp0 + 1

    # fallback path: no draft weights -> degrade to the n-gram drafter
    paddle.seed(4)
    draft2 = PagedCausalLM(PagedServingConfig(**BASE))
    draft2.eval()
    engines2, router2, _, pub2 = _mk_fleet(model, n=1)
    engines2[0].set_drafter(DraftModelDrafter(draft2), k=3)
    pub2.publish(params=_perturbed(model, seed=33))
    assert isinstance(engines2[0]._drafter, NGramDrafter)
    assert _cval("serving/spec_drafter_fallbacks") == fb0 + 1


def test_spec_accept_collapse_alarm(model):
    from paddle_tpu.inference.speculative import DraftModelDrafter

    al0 = _cval("serving/spec_accept_alarms")
    paddle.seed(4)
    draft = PagedCausalLM(PagedServingConfig(**BASE))
    draft.eval()
    engines, router, _, pub = _mk_fleet(model, n=1)
    engines[0].set_drafter(DraftModelDrafter(draft), k=3)
    engines[0]._m.spec_accept_rate.set(0.8)       # pre-swap baseline
    pub.publish(params=_perturbed(model, seed=34),
                draft_params=_perturbed(model, seed=35))
    assert pub._accept_baseline[engines[0].name] == pytest.approx(0.8)
    # healthy post-swap rate: no alarm
    engines[0]._m.spec_accept_rate.set(0.7)
    assert pub.check_spec_health() == []
    # collapse below factor * baseline: alarm fires
    engines[0]._m.spec_accept_rate.set(0.1)
    assert pub.check_spec_health() == [engines[0].name]
    assert _cval("serving/spec_accept_alarms") == al0 + 1


# ---------------------------------------------------------------------------
# the ISSUE acceptance run: 3-replica fleet, live int8 publish,
# kill@publish on one replica, NaN-poisoned candidate refused, forced
# rollback — zero requests lost, bitwise per pinned version, one epoch
# ---------------------------------------------------------------------------

def test_acceptance_chaos_publish_rollout(model):
    import time as _t

    ws = "int8"
    new = _perturbed(model, seed=41)

    def factory(idx):
        return _fresh_engine(model, seed=50 + idx, _weight_stream=ws)

    engines = [factory(i) for i in range(3)]
    for i, e in enumerate(engines):
        e.fault_rank = i
    router = ReplicaRouter(
        [Replica(e, name=f"r{i}") for i, e in enumerate(engines)])
    sup = FleetSupervisor(router, engine_factory=factory,
                          cfg=FleetSupervisorConfig(backoff_base_s=0.001))
    store = TCPStore("127.0.0.1", 0, is_master=True)
    pub = WeightPublisher(router, model, store=store, supervisor=sup)
    rng = np.random.RandomState(17)
    prompts = [list(rng.randint(1, BASE["vocab_size"], 10))
               for _ in range(9)]
    max_new = 5
    try:
        # continuous wave: first cohort admitted and genuinely decoding
        wave_a = [router.submit(list(p), max_new_tokens=max_new,
                                sampling=SP) for p in prompts[:3]]
        for _ in range(3):
            router.step_all()
        # live int8 publish with one replica killed mid-transfer
        try:
            faults.arm("kill@publish:rank=1")
            rep = pub.publish(params=new)
        finally:
            faults.disarm()
        assert rep.version == 1 and "r1" in rep.missed
        wave_b = [router.submit(list(p), max_new_tokens=max_new,
                                sampling=SP) for p in prompts[3:6]]
        # the dead replica restarts and catches up mid-wave
        sup.pump()
        assert router.replicas[1].engine.active_weight_version == 1
        # a NaN-poisoned candidate is refused at the canary — it never
        # serves a token anywhere
        bad = {k: v.copy() for k, v in new.items()}
        kf = next(k for k, v in bad.items()
                  if np.issubdtype(v.dtype, np.floating))
        pf = bad[kf].astype(np.float32)
        pf.flat[::5] = np.nan
        bad[kf] = pf.astype(bad[kf].dtype)
        with pytest.raises(PublishRejectedError):
            pub.publish(params=bad)
        for r2 in router.replicas:
            assert r2.engine.active_weight_version == 1
            assert r2.engine._staged_weights == {}
        wave_c = [router.submit(list(p), max_new_tokens=max_new,
                                sampling=SP) for p in prompts[6:]]
        out = router.run_to_completion()
        sup.pump()
        # zero requests lost: every admitted stream ran to completion
        handles = wave_a + wave_b + wave_c
        assert all(len(out[h]) == max_new for h in handles), out
        # fleet converged on one version epoch
        assert {r2.engine.active_weight_version
                for r2 in router.replicas} == {1}
        # token-bitwise identity per pinned version, every stream
        for h, prompt in zip(handles, prompts):
            idx, rid = router._handles[h]
            eng = router.replicas[idx].engine
            r = eng._requests[rid]
            seed = eng.seed if r.salt_seed is None else r.salt_seed
            assert out[h] == _regen(
                model, prompt, r.salt_rid, seed, max_new,
                version=r.weight_version,
                params=new if r.weight_version else None, ws=ws), \
                f"stream {h} not bitwise under v{r.weight_version}"
        # forced rollback: fleet returns to v0, bitwise
        prev = pub.rollback(reason="forced")
        assert prev == 0
        assert {r2.engine.active_weight_version
                for r2 in router.replicas} == {0}
        h = router.submit(prompts[0], max_new_tokens=max_new,
                          sampling=SP)
        out2 = router.run_to_completion()
        idx, rid = router._handles[h]
        eng = router.replicas[idx].engine
        r = eng._requests[rid]
        assert out2[h] == _regen(model, prompts[0], r.salt_rid,
                                 eng.seed, max_new, ws=ws)
    finally:
        store.close()
