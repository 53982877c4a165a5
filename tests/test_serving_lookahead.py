"""One step in flight: `ServingEngine.step()` call k dispatches step k and
only then fetches step k-1. Every request's token stream must be bit for
bit the serial engine's (a `step(); settle()` loop IS the serial engine),
and everything that reads or moves a request's state from outside the
plain step must find it settled. Over `PagedCausalLM` and the toy hybrid
model of `tests/test_nemotron_h.py` (a row slot beside the pages)."""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.resilience import faults
from paddle_tpu.distributed.resilience.errors import (EngineDeadError,
                                                      TransportClosedError)
from paddle_tpu.inference import disagg, replica_host
from paddle_tpu.inference.fleet_supervisor import (FleetSupervisor,
                                                   FleetSupervisorConfig,
                                                   LoopbackTransport)
from paddle_tpu.inference.router import Replica, ReplicaRouter
from paddle_tpu.inference.serving import (PagedCausalLM, PagedServingConfig,
                                          SamplingParams, ServingEngine)
from paddle_tpu.inference.speculative import NGramDrafter
from paddle_tpu.inference.weight_publish import build_weight_set
from paddle_tpu.jit import functional as FB
from paddle_tpu.profiler import metrics, tracing

from test_nemotron_h import make_model as make_hybrid_model

KINDS = ("paged", "hybrid")
VOCAB = 97
SAMPLED = SamplingParams(temperature=0.8, top_k=20, top_p=0.9)
_models = {}


def model_of(kind):
    if kind not in _models:
        if kind == "paged":
            paddle.seed(42)
            _models[kind] = PagedCausalLM(engine_cfg(kind))
        else:
            _models[kind] = make_hybrid_model()
    return _models[kind]


def engine_cfg(kind, **over):
    if kind == "paged":
        kw = dict(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                  num_heads=4, ffn_size=64, block_size=8, num_blocks=65,
                  max_batch=4, max_blocks_per_seq=8, token_budget=32)
    else:
        kw = dict(vocab_size=128, hidden_size=64, num_layers=11,
                  num_heads=4, num_kv_heads=2, block_size=8, num_blocks=65,
                  max_batch=4, max_blocks_per_seq=8, token_budget=32,
                  dtype="float32")
    kw.update(over)
    return PagedServingConfig(**kw)


def make_engine(kind, seed=5, **over):
    return ServingEngine.from_model(model_of(kind), engine_cfg(kind, **over),
                                    seed=seed)


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n).tolist() for n in lengths]


def count(name):
    return metrics.snapshot()["counters"].get(name, 0)


def drain(eng, serial=False, late=(), late_at=3, **kw):
    """Step the engine dry; `late` requests join after `late_at` calls.
    Returns ({rid: tokens as step() returned them}, calls)."""
    got, calls = {}, 0
    while eng.pending():
        out = eng.step()
        if serial:
            out += eng.settle()
        for rid, tok in out:
            got.setdefault(rid, []).append(tok)
        calls += 1
        if calls == late_at:
            for p in late:
                eng.add_request(p, **kw)
    return got, calls


def idle(eng, kind):
    """Every page and every row slot is back."""
    assert len(eng._free_pages) == eng.cfg.num_blocks - 1
    assert kind == "paged" or len(eng._free_slots) == eng.cfg.max_batch
    assert eng._flight is None and not eng.pending()


# -- the streams ---------------------------------------------------------

@pytest.mark.parametrize("sampling", [None, SAMPLED],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", KINDS)
def test_streams_equal_the_serial_engine(kind, sampling):
    """Chunked prefill (40 tokens against a budget of 32) beside decode
    rows, requests joining mid-flight: what `step()` returns, and what the
    requests hold, is the `step(); settle()` loop's, token for token."""
    ps = prompts(5, 40, 9, 23, 3)
    runs = []
    for serial in (False, True):
        eng = make_engine(kind)
        for i, p in enumerate(ps[:3]):
            eng.add_request(p, max_new_tokens=6 + i, sampling=sampling)
        ahead0, steps0 = count("serving/lookahead_steps"), count(
            "serving/steps")
        got, calls = drain(eng, serial, late=ps[3:], max_new_tokens=5,
                           sampling=sampling)
        held = {rid: list(r.generated) for rid, r in eng._requests.items()}
        assert got == held
        idle(eng, kind)
        runs.append((held, calls, count("serving/steps") - steps0,
                     count("serving/lookahead_steps") - ahead0))
    (ahead, calls, steps, n_ahead), (serial, _, steps_serial, n_serial) = runs
    assert ahead == serial
    assert sorted(len(t) for t in ahead.values()) == [5, 5, 6, 7, 8]
    # the last call only fetches; every dispatch but the first found the
    # step before it unsettled. (Five requests for four rows: the row a
    # finished request gives up is free one step later than on the serial
    # engine, which may cost the fifth a step; the streams do not care.)
    assert calls == steps + 1 and steps_serial <= steps <= steps_serial + 1
    assert (n_ahead, n_serial) == (steps - 1, 0)


@pytest.mark.parametrize("kind", KINDS)
def test_pending_holds_while_a_token_is_in_flight(kind):
    """A request of one token: the call that dispatches its step returns
    nothing and leaves it pending with its settled fields untouched; the
    next call returns the token. `run_to_completion` returns all of them,
    also when its `max_steps` runs out with a step in flight."""
    (p,) = prompts(7)
    eng = make_engine(kind)
    rid = eng.add_request(p, max_new_tokens=1)
    r = eng._requests[rid]
    assert eng.step() == []
    assert eng.pending() == [r] and not r.done
    assert (r.generated, r.cached, r.ahead) == ([], 0, len(p))
    (out,) = eng.step()
    assert out == (rid, r.generated[0]) and r.done and not eng.pending()
    idle(eng, kind)
    rid2 = eng.add_request(p, max_new_tokens=4)
    assert eng.run_to_completion()[rid2][:1] == r.generated
    eng.add_request(p, max_new_tokens=4)
    capped = eng.run_to_completion(max_steps=2)       # prefill + one decode
    assert len(capped[rid2 + 1]) == 2 and eng._flight is None


@pytest.mark.parametrize("kind", KINDS)
def test_a_row_whose_last_token_is_in_flight_is_not_scheduled(kind):
    """Stops by count are known ahead: three tokens are three rows in
    three steps (`serving/step_rows`), and the fourth call dispatches
    nothing. Nothing asked for a settle."""
    (p,) = prompts(11)
    eng = make_engine(kind)
    eng.add_request(p, max_new_tokens=3)
    rows0, steps0 = count("serving/step_rows"), count("serving/steps")
    forced0 = count("serving/settle_forced")
    got, calls = drain(eng)
    assert calls == 4 and len(got[0]) == 3
    assert count("serving/step_rows") - rows0 == 3
    assert count("serving/steps") - steps0 == 3
    assert count("serving/settle_forced") == forced0


# -- end of stream, one step late ----------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_an_end_of_stream_token_is_seen_one_step_late(kind):
    """A stop by `eos_token_id`: the row already sits in the next step
    when the token is fetched. That step's token for it is dropped, every
    page and slot comes back, and a request admitted onto the freed pages
    while that step is still in flight reads as on a fresh engine."""
    a, b = prompts(13, 21, seed=3)
    eng = make_engine(kind)
    eng.add_request(a, max_new_tokens=8)
    whole = eng.run_to_completion()[0]
    eos = whole[3]
    cut = whole[:whole.index(eos) + 1]

    fresh = make_engine(kind)
    fresh.add_request(b, max_new_tokens=6)
    alone = fresh.run_to_completion()[0]

    eng = make_engine(kind)
    rid = eng.add_request(a, max_new_tokens=8, eos_token_id=eos)
    ra = eng._requests[rid]
    rows0 = count("serving/step_rows")
    got, a_pages = [], set()
    while not ra.done:
        a_pages |= set(ra.pages)
        got += eng.step()
    # the stop was seen with the next step dispatched: one row more than
    # tokens, and that step still holds the finished request
    assert [t for _, t in got] == cut == ra.generated
    assert count("serving/step_rows") - rows0 == len(cut) + 1
    assert eng._flight is not None and eng._flight.rows[0][0] is ra
    assert ra.pages == [] and ra.slot is None
    rb = eng._requests[eng.add_request(b, max_new_tokens=6)]
    eng.step()
    assert set(rb.pages) & a_pages
    got, _ = drain(eng)
    assert got == {rb.rid: alone} and ra.generated == cut
    idle(eng, kind)


# -- where the look-ahead gives way --------------------------------------

def alone_streams(kind, ps, **kw):
    out = []
    for p in ps:
        eng = make_engine(kind)
        eng.add_request(p, **kw)
        out.append(eng.run_to_completion()[0])
    return out


@pytest.mark.parametrize("kind", KINDS)
def test_preemption_schedules_from_settled_state(kind):
    """A pool too small for two long requests: no row can go ahead of the
    tokens in flight, the engine settles, and pre-empts from what it then
    holds. Both serve what they serve alone."""
    ps = prompts(30, 30, seed=5)
    alone = alone_streams(kind, ps, max_new_tokens=20)
    pre0, forced0 = count("serving/preemptions"), count(
        "serving/settle_forced")
    eng = make_engine(kind, num_blocks=11)         # 10 pages of 8 tokens
    for p in ps:
        eng.add_request(p, max_new_tokens=20)
    got, _ = drain(eng)
    assert [got[0], got[1]] == alone
    assert count("serving/preemptions") > pre0
    assert count("serving/settle_forced") > forced0
    assert len(eng._free_pages) == 10 and eng._flight is None


@pytest.mark.parametrize("kind", KINDS)
def test_a_deadline_eviction_takes_the_settled_request(kind):
    """A request past its deadline with a token in flight is evicted with
    that token settled: its stream is the serial engine's at that step,
    and its pages and slot are back."""
    (p,) = prompts(9, seed=2)
    evicted = []
    for serial in (False, True):
        eng = make_engine(kind)
        rid = eng.add_request(p, max_new_tokens=12, deadline_s=3600.0)
        r = eng._requests[rid]
        for _ in range(4):
            eng.step()
            if serial:
                eng.settle()
        forced0 = count("serving/settle_forced")
        r.deadline_t = time.perf_counter() - 1.0
        out = eng.step()
        assert r.timed_out and r.done and eng.timed_out_requests() == [rid]
        assert len(r.generated) == 4 and eng._flight is None
        if not serial:
            assert out == [(rid, r.generated[-1])]
            assert count("serving/settle_forced") == forced0 + 1
        idle(eng, kind)
        evicted.append(list(r.generated))
    assert evicted[0] == evicted[1]


def test_a_drafter_reads_settled_tokens():
    """Set while a step is in flight, a drafter settles it; while it is
    set every step settles before it returns, and the speculative steps
    give the plain stream."""
    ps = prompts(6, 17, seed=8)
    plain = alone_streams("paged", ps, max_new_tokens=10,
                          sampling=SAMPLED)
    eng = make_engine("paged")
    for p in ps:
        eng.add_request(p, max_new_tokens=10, sampling=SAMPLED)
    eng.step()
    forced0, spec0 = count("serving/settle_forced"), count(
        "serving/spec_steps")
    drafter = NGramDrafter(block_size=eng.cfg.block_size)
    for p, toks in zip(ps, plain):
        drafter.observe(p + toks)
    eng.set_drafter(drafter, k=3)
    assert eng._flight is None
    assert count("serving/settle_forced") == forced0 + 1
    # (alone each was request 0: other salts. The reference is the same
    # pair on an engine with no drafter)
    ref = make_engine("paged")
    for p in ps:
        ref.add_request(p, max_new_tokens=10, sampling=SAMPLED)
    want = ref.run_to_completion()
    got, _ = drain(eng)
    assert {rid: eng._requests[rid].generated for rid in got} == want
    assert count("serving/spec_steps") > spec0


def test_decode_run_settles_first():
    """The fused window reads each row's newest token: it fetches the
    step in flight first and returns its tokens before its own."""
    ps = prompts(6, 12, seed=9)
    ref = make_engine("paged")
    eng = make_engine("paged")
    for e in (ref, eng):
        for p in ps:
            e.add_request(p, max_new_tokens=9)
    want = ref.run_to_completion()
    got = eng.step() + eng.step()
    assert eng._flight is not None
    forced0 = count("serving/settle_forced")
    got += eng.decode_run(4)
    assert count("serving/settle_forced") == forced0 + 1
    assert len(got) == 2 * (1 + 1 + 4)
    streams = {rid: [t for r, t in got if r == rid] for rid in (0, 1)}
    done = eng.run_to_completion()
    assert done == want
    assert all(done[rid][:6] == streams[rid] for rid in (0, 1))


def test_a_migration_ships_the_settled_request():
    """`disagg.migrate_request` with a step in flight: the token is
    fetched first, the hand-off carries it, and the stream finishes on
    the peer as on one engine."""
    (p,) = prompts(14, seed=4)
    ref = make_engine("paged")
    ref.add_request(p, max_new_tokens=7, sampling=SAMPLED)
    want = ref.run_to_completion()[0]
    src, dst = make_engine("paged"), make_engine("paged", seed=77)
    rid = src.add_request(p, max_new_tokens=7, sampling=SAMPLED)
    src.step()
    src.step()
    r = src._requests[rid]
    assert len(r.generated) == 1 and r.ahead == 1
    forced0 = count("serving/settle_forced")
    tp = LoopbackTransport()
    disagg.migrate_request(src, rid, tp, dst=1)
    assert count("serving/settle_forced") == forced0 + 1
    assert r.done and r.generated == want[:2] and src._flight is None
    new = disagg.receive_request(dst, tp, src=0)
    assert dst.run_to_completion()[new] == want
    idle(src, "paged")
    idle(dst, "paged")


# -- a kill with a step in flight ----------------------------------------

def fleet(kind):
    def factory(idx):
        eng = make_engine(kind, seed=10 + idx)
        eng.fault_rank = idx
        return eng

    router = ReplicaRouter([Replica(factory(i), name=f"r{i}",
                                    restore_after=2) for i in range(2)])
    sup = FleetSupervisor(router, engine_factory=factory,
                          cfg=FleetSupervisorConfig(backoff_base_s=0.0))
    return router, sup


def submit_wave(router):
    return [router.submit(p, max_new_tokens=6, sampling=SAMPLED)
            for p in prompts(9, 11, 7, 13, seed=31)]


@pytest.mark.parametrize("kind", KINDS)
def test_a_kill_leaves_settled_requests_that_finish_elsewhere(kind):
    """`kill@decode` fires with a step in flight: its tokens are not
    emitted, the requests keep the settled state their contract promises,
    migrate (pages) or requeue (a row state does not ship), and finish
    with the tokens of a fleet nobody killed."""
    faults.disarm()
    router, _ = fleet(kind)
    hs = submit_wave(router)
    out = router.run_to_completion()
    want = [out[h] for h in hs]

    faults.arm("kill@decode#3:rank=1")
    try:
        router, sup = fleet(kind)
        hs = submit_wave(router)
        victim = router.replicas[1].engine
        out = router.run_to_completion()
    finally:
        faults.disarm()
    assert victim.dead and victim._flight is None
    assert all(r.ahead == 0 and r.ahead_row == -1
               for r in victim._requests.values())
    assert [out[h] for h in hs] == want
    assert sup.restarts == [0, 1] and sup.drained_handles


def test_a_killed_engine_drops_the_step_in_flight():
    """The engine alone: the kill raises out of the call that would have
    fetched the step in flight; what the request holds is what had been
    returned, a prefix of its stream, at its decode tip."""
    (p,) = prompts(10, seed=6)
    ref = make_engine("paged")
    ref.add_request(p, max_new_tokens=8)
    want = ref.run_to_completion()[0]
    eng = make_engine("paged")
    rid = eng.add_request(p, max_new_tokens=8)
    faults.arm("kill@decode#3:rank=0")
    got = []
    try:
        with pytest.raises(EngineDeadError):
            while True:
                got += eng.step()
    finally:
        faults.disarm()
    r = eng._requests[rid]
    assert [t for _, t in got] == r.generated == want[:len(r.generated)]
    assert len(r.generated) == 2 and r.length - r.cached == 1
    assert (eng._flight, r.ahead, r.ahead_row) == (None, 0, -1)
    assert eng.settle() == []


# -- the stream across a settle that no step asked for ---------------------

def _commit(eng):
    """Publish the model's own weights as version 1 (the streams pinned
    to version 0 drain under it, the tokens do not change)."""
    model = model_of("paged")
    arrays, crcs = build_weight_set(model, FB.current_params(model), eng.cfg)
    eng.stage_weight_set(1, arrays, crcs=crcs)
    eng.commit_weight_set(1)


def _shed(eng):
    with pytest.raises(Exception, match="saturated"):
        eng.add_request([1, 2, 3], max_new_tokens=2)


def _migrate_newest(eng):
    rid = max(r.rid for r in eng.pending())
    tp = LoopbackTransport()
    disagg.migrate_request(eng, rid, tp, dst=1)
    peer = make_engine("paged", seed=77)
    disagg.receive_request(peer, tp, src=0)


ENTRIES = {
    "commit_weight_set": _commit,
    "add_request_at_max_queue": _shed,
    "migrate_request": _migrate_newest,
    "probe_logits": lambda eng: eng.probe_logits([3, 1, 4, 1, 5]),
    "set_drafter": lambda eng: (eng.set_drafter(NGramDrafter(), k=3),
                                eng.set_drafter(None)),
}


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_the_step_stream_is_whole_across_a_forced_settle(entry):
    """Something between two `step()` calls settles the step in flight (a
    weight commit, an admission at `max_queue`, a migration, ...) and has
    no stream to give its tokens to: the next `step()` returns them first,
    so what `step()` returned, taken together, is what each request holds
    (on the source, for the one that migrated)."""
    eng = make_engine("paged", max_queue=3)
    for p in prompts(6, 11, 4, seed=13):
        eng.add_request(p, max_new_tokens=6, sampling=SAMPLED)
    got = {}

    def step():
        for rid, tok in eng.step():
            got.setdefault(rid, []).append(tok)

    step()
    step()
    step()
    assert eng._flight is not None and all(
        len(r.generated) == 2 for r in eng._requests.values())
    forced0 = count("serving/settle_forced")
    ENTRIES[entry](eng)
    assert count("serving/settle_forced") == forced0 + 1
    assert eng._flight is None and eng._held
    held = list(eng._held)
    settled = {rid: list(r.generated) for rid, r in eng._requests.items()}
    assert all(settled[rid][:-1] == got.get(rid, []) for rid, _ in held)
    while eng.pending():
        step()
    step()          # nothing pending: a call still hands over what is held
    assert not eng._held
    want = {rid: list(r.generated) for rid, r in eng._requests.items()
            if r.generated}
    assert got == want


def test_decode_run_and_settle_return_what_was_held():
    """`decode_run()` returns held tokens before its own, and a plain
    `settle()` hands them to its caller, once."""
    ps = prompts(6, 9, seed=3)
    for use in ("decode_run", "settle"):
        ref, eng = make_engine("paged"), make_engine("paged")
        for e in (ref, eng):
            for p in ps:
                e.add_request(p, max_new_tokens=7)
        want = ref.run_to_completion()
        got = eng.step() + eng.step()
        eng.probe_logits([1, 2, 3])
        assert len(eng._held) == 2 and eng.settle(hold=True) == []
        got += eng.decode_run(3) if use == "decode_run" else eng.settle()
        assert not eng._held and eng.settle() == []
        while eng.pending():
            got += eng.step()
        assert {rid: [t for r, t in got if r == rid] for rid in want} == want


@pytest.mark.parametrize("stop", ["count_1", "count_2", "eos_1", "eos_2",
                                  "count_5"])
def test_the_prefill_worker_hands_off_at_the_first_token(stop):
    """`PrefillWorker.pump` fetches each step at once: a request finishes
    there only on its FIRST token (by count or by `eos_token_id`); one
    whose second token would be its last still ships, with one token, and
    finishes on the decode worker with the serial stream. No decode row
    ever runs on the prefill engine."""
    (p,) = prompts(12, seed=21)
    ref = make_engine("paged")
    ref.add_request(p, max_new_tokens=5)
    stream = ref.run_to_completion()[0]
    kw = {"count_1": dict(max_new_tokens=1),
          "count_2": dict(max_new_tokens=2),
          "count_5": dict(max_new_tokens=5),
          "eos_1": dict(max_new_tokens=5, eos_token_id=stream[0]),
          "eos_2": dict(max_new_tokens=5, eos_token_id=stream[1])}[stop]
    assert stream[1] != stream[0]
    want = {"count_1": stream[:1], "count_2": stream[:2],
            "count_5": stream, "eos_1": stream[:1],
            "eos_2": stream[:2]}[stop]
    pre, dec = make_engine("paged"), make_engine("paged", seed=77)
    tp = LoopbackTransport()
    pw = disagg.PrefillWorker(pre, tp, decode_rank=1)
    dw = disagg.DecodeWorker(dec, tp, prefill_rank=0)
    rid = pw.submit(p, **kw)
    decode0 = count("serving/step_tokens") - count(
        "serving/step_prefill_tokens")
    moved = pw.pump()
    assert count("serving/step_tokens") - count(
        "serving/step_prefill_tokens") == decode0
    r = pre._requests[rid]
    assert r.done and r.generated == stream[:1]
    if stop in ("count_1", "eos_1"):
        assert moved == []
    else:
        assert moved == [rid]
        (new,) = dw.accept(1)
        assert dw.run(window=4)[new] == want
    idle(pre, "paged")
    idle(dec, "paged")


def test_a_drain_of_a_live_engine_carries_its_tokens_to_the_router():
    """`FleetSupervisor.drain` of a LIVE engine (a retiring replica)
    settles its step in flight; those tokens reach each handle's stream
    through the router's next `step_all`, ahead of what the peer then
    produces: every stream whole, bit for bit an undrained fleet's."""
    router, _ = fleet("paged")
    hs = submit_wave(router)
    out = router.run_to_completion()
    want = {h: out[h] for h in hs}

    router, sup = fleet("paged")
    hs = submit_wave(router)
    got = {}

    def step():
        for h, toks in router.step_all().items():
            got.setdefault(h, []).extend(toks)

    for _ in range(3):
        step()
    src = router.replicas[1].engine
    live = [r for r in src.pending()]
    assert src._flight is not None and live and all(
        len(r.generated) == 2 for r in live)
    requeues0 = count("serving/drain_requeues")
    assert sup.drain(1) == len(live)
    # each moved at its decode tip, the settled token with it
    assert count("serving/drain_requeues") == requeues0
    assert src._flight is None and not src.pending()
    assert sorted(len(t) for t in router._carried.values()) == [1] * len(live)
    while router._live_pending():
        step()
    assert got == want == router.results()


class _ScriptedParent:
    """The parent's end of a replica host's transport, in this process:
    `script` yields one RPC at a time and is sent each reply."""

    def __init__(self, script):
        self._script, self._reply = script(), None
        self._mailbox = self

    def reserve_recv(self, src, channel):
        return None

    def take(self, tag, timeout):
        try:
            return replica_host.encode(self._script.send(self._reply))
        except StopIteration:
            raise TransportClosedError("script done") from None

    def send(self, payload, dst, channel=None):
        self._reply = replica_host.decode(payload)


def test_a_replica_host_keeps_its_step_in_flight_across_rpcs():
    """The subprocess host's `step` op is the engine's `step()`: the
    child runs ahead as an in-process engine does, the parent's mirror
    follows the settled state one step behind, and the replies taken
    together are every stream whole. `settle` (a drain is coming) hands
    over the tokens in flight."""
    ps = prompts(8, 13, seed=17)
    ref = make_engine("paged")
    for p in ps:
        ref.add_request(p, max_new_tokens=5, sampling=SAMPLED)
    want = ref.run_to_completion()
    eng = make_engine("paged")
    got, seen = {}, {}

    def take(rsp):
        for rid, tok in rsp["produced"]:
            got.setdefault(rid, []).append(tok)

    def script():
        for p in ps:
            rsp = yield {"op": "admit", "prompt": p, "max_new": 5,
                         "sampling": replica_host.encode_sampling(SAMPLED)}
        for _ in range(3):
            rsp = yield {"op": "step"}
            take(rsp)
        seen["in_flight"] = eng._flight is not None
        rsp = yield {"op": "settle"}
        take(rsp)
        seen["settled"] = (eng._flight is None, len(rsp["produced"]))
        while rsp["pending"]:
            rsp = yield {"op": "step"}
            take(rsp)
        seen["done"] = rsp["done"]

    ahead0 = count("serving/lookahead_steps")
    assert replica_host.serve(_ScriptedParent(script), eng) == 0
    assert seen == {"in_flight": True, "settled": (True, 2),
                    "done": [0, 1]}
    assert got == want
    assert count("serving/lookahead_steps") - ahead0 >= 3
    idle(eng, "paged")


# -- the order of a call --------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_step_dispatches_before_it_fetches(kind):
    """Call k's `serving::pack` (which holds the dispatch of step k) ends
    before its `serving::sample_sync`, the wait for step k-1, does; the
    step span says it looked ahead; and the tokens a call returns are the
    previous call's rows'."""
    (p,) = prompts(6, seed=1)
    eng = make_engine(kind)
    rid = eng.add_request(p, max_new_tokens=4)
    order = []
    run_step, fetch = eng._run_step, eng._fetch_flight

    def spy_run(*a, **k):
        order.append("dispatch")
        return run_step(*a, **k)

    def spy_fetch():
        order.append("fetch" if eng._flight is not None else "nothing")
        return fetch()

    eng._run_step, eng._fetch_flight = spy_run, spy_fetch
    mark = len(tracing.ring_spans())
    got, calls = drain(eng)
    assert order == ["dispatch", "nothing"] + ["dispatch", "fetch"] * 3 \
        + ["fetch"]
    spans = tracing.ring_spans()[mark:]
    steps = [s for s in spans if s["name"] == "serving::step"]
    assert [s.get("args", {}).get("lookahead") for s in steps] \
        == [0, 1, 1, 1, None]
    for step in steps[1:4]:
        kids = {s["name"]: s for s in spans
                if s["parent_id"] == step["span_id"]}
        pack, sync = kids["serving::pack"], kids["serving::sample_sync"]
        assert pack["ts"] + pack["dur"] <= sync["ts"] + sync["dur"]
        assert pack["ts"] < sync["ts"] < kids["serving::emit"]["ts"]
    assert len(got[rid]) == 4 and calls == 5
