"""One span path from the program to the profiler's clock (ISSUE 25 A, B):
`RecordEvent` and `tracing.span` reach the xplane as TraceAnnotations, and
the engine's and the trainer's own account of a step (child spans,
counters, `serving/tpot_ms`) adds up."""
import glob
import os

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (PagedCausalLM,
                                          PagedServingConfig,
                                          ServingEngine)
from paddle_tpu.profiler import RecordEvent, metrics, tracing

STEP_PHASES = ("serving::schedule", "serving::pack",
               "serving::sample_sync", "serving::emit")


def _host_events(trace_dir):
    """{name: [(start_ns, end_ns)]} of the trace's host plane."""
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    return out


@pytest.fixture(scope="module")
def engine_parts():
    paddle.seed(7)
    cfg = PagedServingConfig(vocab_size=97, hidden_size=32, num_layers=2,
                             num_heads=4, ffn_size=64, block_size=8,
                             num_blocks=32, max_batch=3,
                             max_blocks_per_seq=6, token_budget=32)
    model = PagedCausalLM(cfg)
    model.eval()
    # warm every program the tests drive, so no trace holds a compile
    eng = ServingEngine.from_model(model, cfg)
    eng.add_request([1, 2, 3], max_new_tokens=2)
    eng.run_to_completion()
    return model, cfg


def _counters():
    return dict(metrics.snapshot()["counters"])


def test_record_event_and_span_reach_the_xplane(tmp_path):
    tracing.clear_ring()
    with jax.profiler.trace(str(tmp_path)):
        with RecordEvent("probe::record_event"):
            pass
        with tracing.span("probe::outer", rows=2):
            with tracing.phase("probe::inner"):
                pass
    host = _host_events(str(tmp_path))
    for name in ("probe::record_event", "probe::outer", "probe::inner"):
        assert len(host[name]) == 1, name
    (o0, o1), (i0, i1) = host["probe::outer"][0], host["probe::inner"][0]
    assert o0 <= i0 and i1 <= o1
    ring = {s["name"]: s for s in tracing.ring_spans()}
    assert ring["probe::inner"]["parent_id"] == ring["probe::outer"]["span_id"]
    assert ring["probe::inner"]["trace_id"] == ring["probe::outer"]["trace_id"]
    assert ring["probe::outer"]["args"] == {"rows": 2}


def test_record_event_outside_a_trace_is_inert():
    ev = RecordEvent("probe::idle")
    with ev:
        assert ev.begin is not None
    assert ev.begin is None
    ev.end()            # a second end is a no-op


def test_phase_spans_stay_out_of_the_flight_recorder():
    tracing.clear_ring()
    before = len([e for e in tracing.flight.events()
                  if e.get("name", "").startswith("probe::flight")])
    with tracing.span("probe::flight_step"):
        with tracing.phase("probe::flight_phase"):
            pass
    mirrored = [e["name"] for e in tracing.flight.events()
                if e.get("name", "").startswith("probe::flight")]
    assert len(mirrored) == before + 1
    assert mirrored[-1] == "probe::flight_step"
    assert {"probe::flight_step", "probe::flight_phase"} <= {
        s["name"] for s in tracing.ring_spans()}


def test_engine_step_children_nest_in_the_xplane(engine_parts, tmp_path):
    model, cfg = engine_parts
    eng = ServingEngine.from_model(model, cfg)
    eng.add_request([5, 6, 7, 8], max_new_tokens=3)
    tracing.clear_ring()
    with jax.profiler.trace(str(tmp_path)):
        eng.run_to_completion()
    host = _host_events(str(tmp_path))
    steps = sorted(host["serving::step"])
    # three steps dispatched; the fourth call only fetches the third's
    # token, and packs nothing
    assert len(steps) == 4
    for name in STEP_PHASES:
        mine = steps[:3] if name == "serving::pack" else steps
        assert len(host[name]) == len(mine), name
        for (c0, c1), (s0, s1) in zip(sorted(host[name]), mine):
            assert s0 <= c0 and c1 <= s1, name
    ring = tracing.ring_spans()
    step_ids = [s["span_id"] for s in ring if s["name"] == "serving::step"]
    for name in STEP_PHASES:
        parents = [s["parent_id"] for s in ring if s["name"] == name]
        assert parents == (step_ids[:3] if name == "serving::pack"
                           else step_ids), name


def test_engine_step_account_adds_up(engine_parts):
    model, cfg = engine_parts
    eng = ServingEngine.from_model(model, cfg)
    rng = np.random.RandomState(0)
    tracing.clear_ring()
    c0 = _counters()
    tpot0 = metrics.snapshot()["histograms"]["serving/tpot_ms"]["count"]
    # 40 prompt tokens pass the 32-token budget: a chunked prefill; one
    # request ends after a single token and observes no gap between tokens
    for n, new in ((40, 4), (9, 6), (3, 1)):
        eng.add_request(list(rng.randint(1, cfg.vocab_size, n)),
                        max_new_tokens=new)
    out = eng.run_to_completion()
    assert sorted(len(v) for v in out.values()) == [1, 4, 6]
    c1 = _counters()
    d = {k: c1.get(k, 0) - c0.get(k, 0) for k in c1}
    steps = d["serving/steps"]
    assert steps > 0
    assert d["serving/step_tokens"] + d["serving/step_pad_tokens"] \
        == steps * cfg.token_budget
    assert d["serving/step_prefill_tokens"] == 40 + 9 + 3
    # every token fed: the prompts, and each generated token but the last
    assert d["serving/step_tokens"] == (40 + 9 + 3) + (3 + 5 + 0)
    assert 0 < d["serving/step_rows"] <= steps * cfg.max_batch
    tpot1 = metrics.snapshot()["histograms"]["serving/tpot_ms"]["count"]
    assert tpot1 - tpot0 == 2          # the two requests with > 1 token

    ring = tracing.ring_spans()
    step_spans = [s for s in ring if s["name"] == "serving::step"]
    # the last call dispatched nothing: it fetched the last step's tokens
    assert len(step_spans) == steps + 1 and "args" not in step_spans.pop()
    assert [s["args"]["lookahead"] for s in step_spans] \
        == [0] + [1] * (steps - 1)
    assert d["serving/lookahead_steps"] == steps - 1
    assert sum(s["args"]["tokens"] for s in step_spans) \
        == d["serving/step_tokens"]
    assert sum(s["args"]["rows"] for s in step_spans) \
        == d["serving/step_rows"]
    for st in step_spans:
        assert st["args"]["tokens"] + st["args"]["pad"] == cfg.token_budget
        kids = [s for s in ring if s["parent_id"] == st["span_id"]]
        assert {k["name"] for k in kids} <= set(STEP_PHASES)
        assert sum(k["dur"] for k in kids) <= st["dur"]
    for r in eng._requests.values():
        assert r.first_tok_t <= r.last_tok_t


def test_decode_run_observes_tpot_as_the_step_paths_do(engine_parts):
    """`serving/tpot_ms` has one meaning whatever path served a request:
    (last token - first token) / (tokens - 1), once, when it finishes."""
    model, cfg = engine_parts
    eng = ServingEngine.from_model(model, cfg)
    hist = lambda: metrics.snapshot()["histograms"]["serving/tpot_ms"]
    h0 = hist()
    rid = eng.add_request([1, 2, 3, 4, 5], max_new_tokens=6)
    assert eng.step() == []                # the prompt, dispatched
    assert len(eng.decode_run(2)) == 3     # its first token, and a window
    assert hist()["count"] == h0["count"]  # a window alone observes nothing
    eng.decode_run(3)
    req = eng._requests[rid]
    assert req.done and len(req.generated) == 6
    h1 = hist()
    assert h1["count"] - h0["count"] == 1
    assert h1["sum"] - h0["sum"] == pytest.approx(
        (req.last_tok_t - req.first_tok_t) / 5 * 1e3)


def test_spec_step_keeps_the_same_account(engine_parts):
    from paddle_tpu.inference.speculative import NGramDrafter

    model, cfg = engine_parts
    eng = ServingEngine.from_model(model, cfg)
    eng.set_drafter(NGramDrafter(block_size=cfg.block_size), k=3)
    tracing.clear_ring()
    c0 = _counters()
    eng.add_request([4, 5, 4, 5, 4, 5, 4], max_new_tokens=8)
    eng.run_to_completion()
    c1 = _counters()
    assert c1["serving/spec_steps"] > c0.get("serving/spec_steps", 0)
    ring = tracing.ring_spans()
    step_spans = [s for s in ring if s["name"] == "serving::step"]
    assert len(step_spans) == c1["serving/steps"] - c0["serving/steps"]
    assert sum(s["args"]["tokens"] + s["args"]["pad"] for s in step_spans) \
        == (c1["serving/step_tokens"] - c0["serving/step_tokens"]
            + c1["serving/step_pad_tokens"] - c0["serving/step_pad_tokens"])
    for st in step_spans:
        kids = [s["name"] for s in ring if s["parent_id"] == st["span_id"]]
        assert kids.count("serving::pack") == 1


def test_arrival_time_starts_the_queue_span_and_ttft(engine_parts):
    import time

    model, cfg = engine_parts
    eng = ServingEngine.from_model(model, cfg)
    tracing.clear_ring()
    h0 = metrics.snapshot()["histograms"]["serving/ttft_ms"]
    now = time.perf_counter()
    late = eng.add_request([1, 2, 3], max_new_tokens=1,
                           arrival_t=now - 5.0, deadline_s=60.0)
    plain = eng.add_request([1, 2, 4], max_new_tokens=1)
    assert eng._requests[late].submit_t == pytest.approx(now - 5.0)
    assert eng._requests[plain].submit_t >= now
    # the deadline still counts from the call, not from the arrival
    assert eng._requests[late].deadline_t >= now + 60.0
    eng.run_to_completion()
    h1 = metrics.snapshot()["histograms"]["serving/ttft_ms"]
    assert h1["count"] - h0["count"] == 2
    assert h1["sum"] - h0["sum"] >= 5000.0
    queue = {s["args"]["rid"]: s for s in tracing.ring_spans()
             if s["name"] == "serving::queue"}
    assert queue[late]["dur"] >= 5.0 > queue[plain]["dur"]


def test_router_hands_the_arrival_time_to_the_engine(engine_parts):
    import time

    from paddle_tpu.inference import Replica, ReplicaRouter

    model, cfg = engine_parts
    router = ReplicaRouter([Replica(ServingEngine.from_model(model, cfg),
                                    name="a")])
    arrived = time.perf_counter() - 2.0
    h = router.submit([1, 2, 3], max_new_tokens=1, arrival_t=arrived)
    idx, rid = router._handles[h]
    assert router.replicas[idx].engine._requests[rid].submit_t \
        == pytest.approx(arrived)


def test_trainer_step_spans():
    import dataclasses

    from paddle_tpu.distributed.fleet.trainer import HybridTrainer
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models import llama

    cfg = dataclasses.replace(llama.LLAMA_PRESETS["debug"],
                              num_hidden_layers=1)
    tr = HybridTrainer(cfg, build_mesh(devices=jax.devices()[:1]))
    ids = np.arange(2 * 16, dtype=np.int32).reshape(2, 16) % cfg.vocab_size
    tracing.clear_ring()
    for _ in range(2):
        tr.step(ids, ids)
    ring = tracing.ring_spans()
    steps = [s for s in ring if s["name"] == "trainer::step"]
    assert len(steps) == 2
    for st in steps:
        kids = [s for s in ring if s["parent_id"] == st["span_id"]]
        assert [k["name"] for k in kids] == ["trainer::place_batch",
                                             "trainer::dispatch"]
        assert sum(k["dur"] for k in kids) <= st["dur"]
