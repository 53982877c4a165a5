"""The three readers of the program's own account (ISSUE 25 D), on a
hand-built trace, span ring and counter registry with known answers; None
where there is nothing to read and where the attribution is under its
limits."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import bench_rehearse as br      # noqa: F401  (puts the repo on sys.path)
from benchmark import harness, reduce_trace as rt
from benchmark.readers import (phase_ms_per_step, program_counter_ratio,
                               program_span_ms)
from paddle_tpu.profiler import metrics, scopes, tracing

TRAFFIC = {"batch": 2, "seq": 8}


def _program(name):
    """A registered two-block program and the names of one instruction of
    each of its phases."""
    def fn(x):
        with scopes.scope("mlp"):
            y = jnp.tanh(x @ x)
        with scopes.scope("head_loss"):
            return jnp.sum(y * y)

    jitted = jax.jit(jax.grad(fn))
    x = jnp.ones((8, 8))
    scopes.register_program(name, jitted, scopes.abstract((x,)))
    by_phase = {}
    for instr, phase in scopes.instruction_phases(name).items():
        if phase is not None:
            by_phase.setdefault(phase, instr)
    return by_phase


def _ctx(ops, steps=2, window=(0.0, 10.0)):
    trace = rt.Trace(device_ops={0: ops},
                     annotations=[("bench.trainer_step", *window)])
    stats = {"traced_work": {"tokens": steps * 16, "seq": 8}}
    return harness.ReadContext(trace, window, {}, TRAFFIC, {}, 1, stats)


def _event(instr, start, end):
    return (f"%{instr} = f32[8,8]{{1,0}} fusion(f32[8,8]{{1,0}} %p)",
            start, end)


def test_phase_reader_known_answers():
    by_phase = _program("readers_probe")
    fwd = by_phase[("forward", "mlp")]
    bwd_mlp = by_phase[("backward", "mlp")]
    bwd_head = by_phase[("backward", "head_loss")]
    ops = [_event(fwd, 0.0, 1.0), _event(bwd_mlp, 1.0, 4.0),
           _event(bwd_head, 4.0, 6.0),
           _event(bwd_mlp, 9.5, 10.5)]      # passes the window: left out
    ctx = _ctx(ops)

    def read(**params):
        return phase_ms_per_step.read(
            {"program": "readers_probe", **params}, ctx)

    assert read(**{"pass": "backward"}) == pytest.approx(5.0 / 2 * 1e3)
    assert read(**{"pass": "forward"}) == pytest.approx(1.0 / 2 * 1e3)
    assert read(block="head_loss") == pytest.approx(2.0 / 2 * 1e3)
    assert read(block="mlp") == pytest.approx(4.0 / 2 * 1e3)
    assert read(**{"pass": "backward", "block": "mlp"}) \
        == pytest.approx(3.0 / 2 * 1e3)
    assert read(**{"pass": "optimizer"}) == 0.0     # read, and nothing there
    assert read() == pytest.approx(6.0 / 2 * 1e3)


def test_phase_reader_returns_none_under_its_limits():
    by_phase = _program("readers_probe")
    bwd = by_phase[("backward", "mlp")]
    bare = next(i for i, p in scopes.instruction_phases(
        "readers_probe").items() if p is None)
    params = {"program": "readers_probe", "pass": "backward"}
    good = [_event(bwd, 0.0, 9.0)]
    assert phase_ms_per_step.read(params, _ctx(good)) is not None
    # 2% of the time in an instruction the program does not have
    alien = good + [_event("not_in_program.9", 9.0, 9.2)]
    assert phase_ms_per_step.read(params, _ctx(alien)) is None
    # found, but 10% of it carries no op_name
    unnamed = good + [_event(bare, 9.0, 10.0)]
    assert phase_ms_per_step.read(params, _ctx(unnamed)) is None
    # 4% without an op_name is inside the limit
    some = good + [_event(bare, 9.0, 9.3)]
    assert phase_ms_per_step.read(params, _ctx(some)) \
        == pytest.approx(9.0 / 2 * 1e3)
    # a phase the map only inherited counts as unnamed against the limit:
    # 3% inherited beside 1% bare still reads, the inherited time included
    phases = scopes.instruction_phases("readers_probe")
    phases["guessed.1"] = ("backward", "mlp")
    phases.inherited.add("guessed.1")
    try:
        few = [_event(bwd, 0.0, 9.6), _event("guessed.1", 9.6, 9.9),
               _event(bare, 9.9, 10.0)]
        assert phase_ms_per_step.read(params, _ctx(few)) \
            == pytest.approx(9.9 / 2 * 1e3)
        # 3% inherited beside 4% bare does not, though each alone would
        many = [_event(bwd, 0.0, 9.3), _event("guessed.1", 9.3, 9.6),
                _event(bare, 9.6, 10.0)]
        assert phase_ms_per_step.read(params, _ctx(many)) is None
    finally:
        del phases["guessed.1"]
        phases.inherited.discard("guessed.1")
    # nothing to read: no such program, no traced work, no events
    assert phase_ms_per_step.read(
        {"program": "never_registered"}, _ctx(good)) is None
    ctx = _ctx(good)
    ctx.stats = {}
    assert phase_ms_per_step.read(params, ctx) is None
    assert phase_ms_per_step.read(params, _ctx([])) is None


def test_span_reader_takes_the_median_of_the_traced_spans():
    tracing.clear_ring()
    assert program_span_ms.read({"span": "probe::reader"}, None) is None
    for dur in (0.001, 0.002, 0.010):
        tracing.record_span("probe::reader", 1.0, 1.0 + dur, traced=True)
    # warm-up, ramp and drain: not under the device trace, so not read
    for dur in (0.5, 0.6, 0.7, 0.8):
        tracing.record_span("probe::reader", 1.0, 1.0 + dur)
    tracing.record_span("probe::other", 1.0, 2.0, traced=True)
    tracing.record_span("probe::untraced", 1.0, 2.0)
    assert program_span_ms.read({"span": "probe::reader"}, None) \
        == pytest.approx(2.0)
    assert program_span_ms.read({"span": "probe::absent"}, None) is None
    assert program_span_ms.read({"span": "probe::untraced"}, None) is None


def test_span_reader_reads_the_steps_a_device_trace_covered(tmp_path):
    """End to end on the CPU: of six steps, the two that ran under
    `jax.profiler.trace` are the ones read."""
    import time

    def step(seconds):
        with tracing.span("probe::traced_step"):
            time.sleep(seconds)

    tracing.clear_ring()
    step(0.05)
    step(0.05)
    with jax.profiler.trace(str(tmp_path)):
        step(0.001)
        step(0.001)
    step(0.05)
    step(0.05)
    spans = [s for s in tracing.ring_spans()
             if s["name"] == "probe::traced_step"]
    assert [bool(s.get("traced")) for s in spans] \
        == [False, False, True, True, False, False]
    assert program_span_ms.read({"span": "probe::traced_step"}, None) < 25.0


def test_counter_reader_ratios():
    reg = metrics.registry()
    reg.counter("probe/readers_num").inc(30)
    reg.counter("probe/readers_pad").inc(90)
    reg.counter("probe/readers_steps").inc(10)
    reg.counter("probe/readers_zero")
    read = program_counter_ratio.read
    assert read({"num": ["probe/readers_num"],
                 "den": ["probe/readers_steps"]}, None) == pytest.approx(3.0)
    assert read({"num": ["probe/readers_num"],
                 "den": ["probe/readers_num", "probe/readers_pad"],
                 "scale": 100}, None) == pytest.approx(25.0)
    assert read({"num": ["probe/readers_num"],
                 "den": ["probe/readers_never_made"]}, None) is None
    assert read({"num": ["probe/readers_num"],
                 "den": ["probe/readers_zero"]}, None) is None


def test_the_new_metrics_name_what_the_program_writes():
    """Each new metric's file points its reader at a span, a counter or a
    program that the program really has."""
    from paddle_tpu.distributed.fleet import trainer       # noqa: F401
    from paddle_tpu.inference.serving import _EngineMetrics

    _EngineMetrics(metrics.registry())
    known = set(metrics.snapshot()["counters"])
    spans = {"trainer::step", "trainer::place_batch", "trainer::dispatch",
             "serving::schedule", "serving::pack", "serving::emit"}
    checked = 0
    for m in harness.load_manifest()["per_layer"]:
        path = os.path.join(harness.BENCH_DIR, "layer_metrics",
                            m["name"] + ".json")
        with open(path) as f:
            spec = json.load(f)
        params = spec["params"]
        if spec["reader"] == "program_span_ms":
            assert m["source"] == "program_span"
            assert params["span"] in spans, m["name"]
        elif spec["reader"] == "program_counter_ratio":
            assert m["source"] == "program_counter"
            assert set(params["num"]) | set(params["den"]) <= known
            assert "whole run" in spec["covers"]      # until windowed
        elif spec["reader"] == "phase_ms_per_step":
            assert m["source"] == "device_trace"
            assert params["program"] == "train_step"
            assert params.get("pass") in (None, *scopes.PASSES)
        else:
            continue
        checked += 1
    assert checked >= 14


def test_every_counter_and_phase_span_this_pr_added_has_a_reader():
    """A counter or a per-step span that nothing reads costs a step and
    tells nobody: each is named by some metric's file (`serving::step` and
    `serving::sample_sync` are read by operators and by the idle-gap split,
    PERF.md section 5)."""
    read = set()
    for name in os.listdir(os.path.join(harness.BENCH_DIR,
                                        "layer_metrics")):
        with open(os.path.join(harness.BENCH_DIR, "layer_metrics",
                               name)) as f:
            params = json.load(f).get("params", {})
        read |= {params.get("span")} | set(params.get("num", ())) \
            | set(params.get("den", ()))
    assert {"serving/step_rows", "serving/step_tokens",
            "serving/step_pad_tokens", "serving/step_prefill_tokens",
            "trainer::step", "trainer::place_batch", "trainer::dispatch",
            "serving::schedule", "serving::pack", "serving::emit"} <= read
