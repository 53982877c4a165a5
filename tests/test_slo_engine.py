"""Fleet SLO engine (ISSUE 16): telemetry timeline, burn-rate
alerting, and capacity-headroom signals.

Three composable pieces over the observability plane:

  * `profiler/timeline.py` — bounded time-series ring over registry
    snapshots: per-window digest retention (honest t-digest window
    quantiles, not averages of averages), counter rates, point events,
    manifest-gated JSONL spill, flight-dump embedding.
  * `profiler/slo.py` — per-(tenant × class) objectives over the
    gateway's new reason-coded terminal outcomes, attainment
    accounting, multi-window burn-rate alerts with raise/clear
    hysteresis.
  * `profiler/headroom.py` — `ScaleAdvisor` fitting the recorded
    load-vs-goodput curve; monotone scale advisories (the AutoScaler
    input interface).

Everything runs on injectable synthetic clocks — wall-clock never
enters a window boundary or an alert decision in this file.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.resilience import faults
from paddle_tpu.distributed.resilience.errors import GatewayRejectedError
from paddle_tpu.inference.gateway import (BrownoutConfig,
                                          BrownoutController,
                                          FleetGateway, GatewayConfig,
                                          SLOClassConfig, TenantConfig,
                                          L_REJECT, L_SHED,
                                          default_classes)
from paddle_tpu.inference.router import Replica, ReplicaRouter
from paddle_tpu.inference.serving import (PagedCausalLM,
                                          PagedServingConfig,
                                          SamplingParams, ServingEngine)
from paddle_tpu.profiler import metrics as _metrics
from paddle_tpu.profiler import timeline as _timeline
from paddle_tpu.profiler import tracing as _tracing
from paddle_tpu.profiler.aggregate import FleetAggregator
from paddle_tpu.profiler.digest import QuantileDigest
from paddle_tpu.profiler.headroom import ScaleAdvisor
from paddle_tpu.profiler.slo import SLOObjective, SLOTracker
from paddle_tpu.profiler.timeline import Timeline, load_spill

BASE = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
            num_kv_heads=2, ffn_size=64, block_size=8, num_blocks=48,
            max_batch=3, max_blocks_per_seq=6, token_budget=32)

SP = SamplingParams(temperature=0.8, top_k=20, top_p=0.95)


@pytest.fixture(autouse=True)
def _clean():
    yield
    faults.disarm()
    _tracing.flight.detach("timeline")
    _tracing.set_flight_dir(None)
    for tl in list(_timeline._sinks):
        _timeline.uninstall(tl)


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    m = PagedCausalLM(PagedServingConfig(**BASE))
    m.eval()
    return m


def _fresh_engine(model, seed=0, **over):
    cfg = PagedServingConfig(**{**BASE, **over})
    return ServingEngine.from_model(model, cfg, seed=seed)


def _classes():
    cls = default_classes()
    for c in cls.values():
        c.deadline_s = None         # determinism, not wall-clock
    return cls


def _fleet(model, gcfg=None, n=2, **over):
    router = ReplicaRouter(
        [Replica(_fresh_engine(model, seed=10 + i, **over),
                 name=f"r{i}") for i in range(n)])
    return FleetGateway(router, gcfg or GatewayConfig(
        classes=_classes())), router


def _unloaded_reference(model, prompt, stream_key, max_new=6):
    """One request alone on a fresh engine under the salt identity the
    gateway pins (stream_key, salt_seed 0)."""
    eng = _fresh_engine(model, seed=99)
    rid = eng.add_request(list(prompt), max_new_tokens=max_new,
                          sampling=SP)
    eng._requests[rid].salt_rid = stream_key
    eng._requests[rid].salt_seed = 0
    while eng.pending():
        eng.step()
    return eng._requests[rid].generated


def _tl(clock, registry=None, **kw):
    return Timeline(registry=registry or _metrics.MetricsRegistry(),
                    clock=clock, **kw)


# ---------------------------------------------------------------------------
# window digests (metrics.py): the drainable second sketch
# ---------------------------------------------------------------------------

def test_histogram_drain_window_is_per_window_and_single_consumer():
    reg = _metrics.MetricsRegistry()
    h = reg.histogram("test/lat_ms")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    w1 = h.drain_window()
    assert w1.count == 3
    # the drain reset the window sketch but not the cumulative one
    assert h.drain_window().count == 0
    assert h.quantile(0.5) is not None
    for v in (10.0, 20.0):
        h.observe(v)
    w2 = h.drain_window()
    assert w2.count == 2
    assert w2.min >= 10.0          # only the NEW observations


# ---------------------------------------------------------------------------
# timeline: rates, series, honest window quantiles, ring + spill
# ---------------------------------------------------------------------------

def test_timeline_rate_and_series_on_synthetic_clock():
    now = [0.0]
    reg = _metrics.MetricsRegistry()
    tl = _tl(lambda: now[0], reg)
    c = reg.counter("test/reqs")
    g = reg.gauge("test/load")
    for i in range(6):
        c.inc(10)
        g.set(float(i))
        now[0] += 10.0
        tl.sample()
    # 10 increments per 10s window — exactly 1.0/s over any window
    assert tl.rate("test/reqs", window_s=20.0) == pytest.approx(1.0)
    assert tl.rate("test/reqs") == pytest.approx(1.0)
    assert tl.rate("test/missing", window_s=20.0) == pytest.approx(0.0)
    s = tl.series("test/load")
    assert [v for _, v in s] == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0]
    # counters fall back to the cumulative value per window
    assert [v for _, v in tl.series("test/reqs")][-1] == 60


def test_timeline_window_p95_matches_numpy_and_is_honest():
    now = [0.0]
    reg = _metrics.MetricsRegistry()
    tl = _tl(lambda: now[0], reg)
    h = reg.histogram("test/lat_ms")
    rng = np.random.RandomState(7)
    slow = rng.uniform(5.0, 10.0, 400)       # window 1: fast era
    fast = rng.uniform(90.0, 110.0, 400)     # window 2: regression era
    for v in slow:
        h.observe(float(v))
    now[0] = 10.0
    tl.sample()
    for v in fast:
        h.observe(float(v))
    now[0] = 20.0
    tl.sample()
    # trailing 10s covers ONLY the regression era: its p95 must be the
    # p95 of that window's stream, not diluted by the fast era
    p95_win = tl.percentile("test/lat_ms", 0.95, window_s=5.0)
    assert p95_win == pytest.approx(np.percentile(fast, 95), rel=0.05)
    # the full-retention quantile merges both windows
    p95_all = tl.percentile("test/lat_ms", 0.95)
    both = np.concatenate([slow, fast])
    assert p95_all == pytest.approx(np.percentile(both, 95), rel=0.05)
    assert p95_win > p95_all       # the dilution the window view avoids


def test_timeline_ring_bound_events_and_spill_replay(tmp_path):
    now = [0.0]
    reg = _metrics.MetricsRegistry()
    tl = _tl(lambda: now[0], reg, capacity=4, spill_dir=str(tmp_path))
    c = reg.counter("test/reqs")
    for i in range(6):
        c.inc()
        tl.event("tick", i=i)
        now[0] += 1.0
        tl.sample()
    assert len(tl.windows()) == 4              # ring bound holds
    evs = tl.events(kind="tick")
    assert [e["i"] for e in evs] == [2, 3, 4, 5]
    # the spill retains ALL 6 windows (the ring only bounds memory)
    replay = load_spill(str(tmp_path))
    assert [w["seq"] for w in replay] == [1, 2, 3, 4, 5, 6]
    # a torn tail line (crash between data append and manifest publish)
    # is ignored: the manifest is the completeness marker
    with open(os.path.join(str(tmp_path), _timeline.SPILL_FILE), "a") as f:
        f.write('{"seq": 7, "t": 6.0, "coun')
    assert len(load_spill(str(tmp_path))) == 6
    # no manifest at all -> nothing is trusted
    os.remove(os.path.join(str(tmp_path), "MANIFEST.json"))
    assert load_spill(str(tmp_path)) == []


def test_timeline_flight_dump_embeds_recent_windows(tmp_path):
    now = [0.0]
    reg = _metrics.MetricsRegistry()
    tl = _tl(lambda: now[0], reg)
    h = reg.histogram("test/lat_ms")
    for i in range(5):
        h.observe(float(i + 1))
        now[0] += 1.0
        tl.sample()
    _tracing.set_flight_dir(str(tmp_path))
    tl.attach_flight(n=3)
    path = _tracing.flight_dump("test_incident")
    with open(path) as f:
        doc = json.load(f)
    wins = doc["timeline"]
    assert [w["seq"] for w in wins] == [3, 4, 5]
    assert wins[-1]["digests"]["test/lat_ms"]["count"] == 1
    assert "p95" in wins[-1]["digests"]["test/lat_ms"]


# ---------------------------------------------------------------------------
# satellite: aggregator staleness eviction
# ---------------------------------------------------------------------------

def _snap(host, rep, values):
    d = QuantileDigest()
    for v in values:
        d.observe(v)
    return {"host_id": host, "replica": rep, "counters": {},
            "gauges": {}, "histograms": {"serving/ttft_ms": {
                "count": len(values), "sum": float(sum(values)),
                "min": min(values), "max": max(values),
                "digest": d.to_dict()}}}


def test_aggregator_evicts_stale_replicas():
    now = [0.0]
    agg = FleetAggregator(clock=lambda: now[0], stale_after_s=60.0)
    evict0 = _metrics.counter("fleet/stale_evictions").value
    agg.ingest(_snap("h0", "r0", [1.0] * 50))
    agg.ingest(_snap("h0", "r1", [1000.0] * 50))
    assert agg.percentile("serving/ttft_ms", 0.95) > 500.0
    now[0] = 100.0
    agg.ingest(_snap("h0", "r0", [1.0] * 50))  # r0 keeps publishing
    evicted = agg.evict_stale()
    assert evicted == [("h0", "r1")]
    assert _metrics.counter("fleet/stale_evictions").value - evict0 == 1
    assert agg.keys() == [("h0", "r0")]
    # the retired replica's final digest no longer pollutes fleet p95
    assert agg.percentile("serving/ttft_ms", 0.95) < 10.0
    # automatic eviction on fleet reads (stale_after_s set)
    now[0] = 300.0
    assert agg.fleet_snapshot()["n_replicas"] == 0


# ---------------------------------------------------------------------------
# SLO tracker: attainment + burn-rate alert state machine
# ---------------------------------------------------------------------------

def _ev(outcome, tenant="acme", slo="interactive", ttft=None,
        reason=None):
    return {"outcome": outcome, "tenant": tenant, "slo": slo,
            "reason": reason, "ttft_ms": ttft, "ticket": None,
            "synthetic": False}


def test_slo_attainment_accounting_with_ttft_bound():
    now = [0.0]
    tr = SLOTracker(class_objectives={
        "interactive": SLOObjective(target=0.99, ttft_ms=100.0)},
        clock=lambda: now[0])
    tr.record(_ev("completed", ttft=50.0))      # good
    tr.record(_ev("drained", ttft=80.0))        # good (drain is good)
    tr.record(_ev("completed", ttft=150.0))     # SLOW: burns budget
    tr.record(_ev("deadline_missed"))           # bad
    tr.record(_ev("shed", slo="best_effort"))   # bad, other class
    assert tr.attainment("acme", "interactive") == pytest.approx(0.5)
    assert tr.attainment(slo="best_effort") == 0.0
    assert tr.attainment() == pytest.approx(2 / 5)
    rep = tr.report()
    row = rep["per_tenant"]["acme/interactive"]
    assert row["total"] == 4 and row["good"] == 2
    assert row["outcomes"] == {"completed": 2, "drained": 1,
                               "deadline_missed": 1}
    assert rep["per_class"]["interactive"]["attainment"] == 0.5


def test_burn_alert_raise_and_clear_hysteresis():
    now = [0.0]
    tr = SLOTracker(clock=lambda: now[0], fast_window_s=10.0,
                    slow_window_s=100.0, burn_threshold=10.0,
                    exit_ratio=0.5, clear_after=3)
    # a healthy hour of traffic
    for t in range(80):
        now[0] = float(t)
        tr.record(_ev("completed"))
    # a single fast-window spike: fast burn is huge, slow burn is not
    # -> multi-window logic must NOT page
    now[0] = 95.0
    for _ in range(8):
        tr.record(_ev("shed"))
    assert tr.evaluate(now=100.0) == []
    assert tr.alerts == []
    # sustained badness: the slow window fills with failures too
    for t in range(100, 160):
        now[0] = float(t)
        tr.record(_ev("shed"))
    active = tr.evaluate(now=160.0)
    assert len(active) == 1 and active[0].active
    assert active[0].tenant == "acme"
    # re-evaluating while hot neither double-raises nor clears
    assert len(tr.evaluate(now=161.0)) == 1
    assert len(tr.alerts) == 1
    # calm evals: clearing needs clear_after=3 CONSECUTIVE calm passes
    assert len(tr.evaluate(now=300.0)) == 1     # calm #1
    assert len(tr.evaluate(now=301.0)) == 1     # calm #2
    now[0] = 302.0
    tr.record(_ev("shed"))                      # one more failure...
    assert len(tr.evaluate(now=302.0)) == 1     # ...resets the streak
    assert len(tr.evaluate(now=320.0)) == 1
    assert len(tr.evaluate(now=321.0)) == 1
    assert tr.evaluate(now=322.0) == []         # calm #3: cleared
    assert len(tr.alerts) == 1 and not tr.alerts[0].active
    assert tr.alerts[0].cleared_t == 322.0
    # quiet aftermath: no flapping back
    assert tr.evaluate(now=400.0) == []
    assert len(tr.alerts) == 1


# ---------------------------------------------------------------------------
# headroom: curve fit + monotone advisories
# ---------------------------------------------------------------------------

def _loaded_timeline(load, n=5, goodput_per_s=8.0):
    now = [0.0]
    reg = _metrics.MetricsRegistry()
    tl = _tl(lambda: now[0], reg)
    g = reg.gauge("gateway/load_score")
    c = reg.counter("gateway/outcome/completed")
    for _ in range(n):
        g.set(load)
        c.inc(int(goodput_per_s * 10))
        now[0] += 10.0
        tl.sample()
    return tl


def test_scale_advisor_monotone_in_load():
    rank = {"scale_down": 0, "hold": 1, "scale_up": 2}
    sweep = [0.05, 0.2, 0.5, 0.9, 1.2, 1.8]
    actions = [ScaleAdvisor(_loaded_timeline(l), window_s=100.0)
               .recommend().action for l in sweep]
    assert actions[0] == "scale_down"
    assert actions[-1] == "scale_up"
    # more load NEVER yields a lazier recommendation
    ranks = [rank[a] for a in actions]
    assert ranks == sorted(ranks)


def test_scale_advisor_alert_and_headroom():
    # an active burn alert forces scale_up even at comfortable load
    now = [0.0]
    tr = SLOTracker(clock=lambda: now[0], fast_window_s=10.0,
                    slow_window_s=100.0)
    for _ in range(50):
        tr.record(_ev("shed"))
    tr.evaluate(now=1.0)
    assert tr.active_alerts()
    adv = ScaleAdvisor(_loaded_timeline(0.5), tracker=tr,
                       window_s=100.0).recommend()
    assert adv.action == "scale_up" and "alert" in adv.reason
    # a recently-cleared alert vetoes scale_down (hold, not shrink)
    for t in range(2, 6):
        tr.evaluate(now=200.0 + t)
    assert not tr.active_alerts()
    tl = _loaded_timeline(0.05, n=25)           # t reaches 250
    adv = ScaleAdvisor(tl, tracker=tr, window_s=100.0).recommend()
    assert adv.action == "hold"
    # headroom falls as load approaches the saturation bound (with a
    # sparse curve the knee falls back to the high_load watermark)
    h_low = ScaleAdvisor(_loaded_timeline(0.2, n=2),
                         window_s=100.0).recommend().headroom
    h_high = ScaleAdvisor(_loaded_timeline(0.9, n=2),
                          window_s=100.0).recommend().headroom
    assert h_low > h_high >= 0.0
    # a fitted knee caps headroom: at the knee itself none remains
    at_knee = ScaleAdvisor(_loaded_timeline(0.5),
                           window_s=100.0).recommend()
    assert at_knee.saturation_load == pytest.approx(0.5)
    assert at_knee.headroom == pytest.approx(0.0)


def test_scale_advisor_drain_candidates_respect_target_load():
    tl = _loaded_timeline(0.05)
    adv = ScaleAdvisor(tl, window_s=100.0, target_load=0.7)
    a = adv.recommend(replica_loads={"r0": 0.05, "r1": 0.1, "r2": 0.6})
    assert a.action == "scale_down"
    assert a.drain_candidates == ["r0", "r1"]   # survivors stay <= 0.7
    # draining must never empty the fleet
    a = adv.recommend(replica_loads={"solo": 0.0})
    assert a.drain_candidates == []


# ---------------------------------------------------------------------------
# gateway outcome events: one reason-coded terminal outcome per request
# ---------------------------------------------------------------------------

def test_gateway_outcome_reason_codes(model):
    gw, _ = _fleet(model, GatewayConfig(
        classes=_classes(),
        tenants={"acme": TenantConfig(rate=1000.0, burst=1000.0),
                 "throttled": TenantConfig(rate=0.0, burst=1.0),
                 "full": TenantConfig(rate=1000.0, burst=1000.0,
                                      max_queued=0)}))
    events = []
    gw.outcome_listeners.append(events.append)
    prompt = list(np.random.RandomState(0).randint(1, 90, 8))

    t0 = gw.submit(prompt, max_new_tokens=4, sampling=SP,
                   tenant="acme", slo="interactive")
    gw.run_to_completion()
    done = [e for e in events if e["outcome"] == "completed"]
    assert len(done) == 1
    assert done[0]["ticket"] == t0
    assert done[0]["tenant"] == "acme"
    assert done[0]["ttft_ms"] is not None and done[0]["ttft_ms"] >= 0
    # completion latches exactly once: further steps re-emit nothing
    gw.step()
    assert len([e for e in events if e["outcome"] == "completed"]) == 1

    with pytest.raises(GatewayRejectedError):
        gw.submit(prompt, tenant="throttled", slo="batch")   # burst=1
        gw.submit(prompt, tenant="throttled", slo="batch")
    assert events[-1]["outcome"] == "rejected"
    assert events[-1]["reason"] == "tenant_rate"

    with pytest.raises(GatewayRejectedError):
        gw.submit(prompt, tenant="full", slo="batch")
    assert events[-1]["reason"] == "tenant_queue_full"

    gw.brownout.level = L_SHED
    with pytest.raises(GatewayRejectedError):
        gw.submit(prompt, tenant="acme", slo="best_effort")
    assert events[-1]["outcome"] == "shed"
    assert events[-1]["reason"] == "brownout_shed"

    gw.brownout.level = L_REJECT
    with pytest.raises(GatewayRejectedError):
        gw.submit(prompt, tenant="acme", slo="batch")
    assert events[-1]["outcome"] == "rejected"
    assert events[-1]["reason"] == "brownout_reject"

    # every event carried the full schema
    for e in events:
        assert set(e) == {"outcome", "reason", "tenant", "slo",
                          "ticket", "synthetic", "ttft_ms"}


def test_gateway_outcome_counters_move(model):
    gw, _ = _fleet(model)
    c0 = _metrics.counter("gateway/outcome/completed").value
    prompt = list(np.random.RandomState(1).randint(1, 90, 8))
    gw.submit(prompt, max_new_tokens=4, sampling=SP,
              tenant="acme", slo="interactive")
    gw.run_to_completion()
    assert _metrics.counter("gateway/outcome/completed").value \
        - c0 == 1


# ---------------------------------------------------------------------------
# flight-dump triggers: sustained brownout reject + quorum loss
# ---------------------------------------------------------------------------

def test_brownout_sustained_reject_dumps_once(tmp_path):
    _tracing.set_flight_dir(str(tmp_path))
    bc = BrownoutController(BrownoutConfig(
        enter_load=1.0, exit_load=0.4, hysteresis=1,
        reject_dump_after=3))
    for _ in range(4):                   # climb clamp->defer->shed...
        bc.observe(2.0)
    assert bc.level == L_REJECT

    def dumps():
        return [f for f in os.listdir(str(tmp_path))
                if "brownout_reject_sustained" in f]

    assert dumps() == []                 # touching reject is not enough
    bc.observe(2.0)
    bc.observe(2.0)                      # held 3 evals -> the black box
    assert len(dumps()) == 1
    for _ in range(5):
        bc.observe(2.0)                  # holding longer: still one dump
    assert len(dumps()) == 1
    # a full recovery re-arms the trigger for the NEXT episode
    for _ in range(10):
        bc.observe(0.0)
    assert bc.level < L_REJECT
    for _ in range(10):
        bc.observe(2.0)
    assert len(dumps()) == 2
    with open(os.path.join(str(tmp_path), dumps()[0])) as f:
        doc = json.load(f)
    assert doc["meta"]["held_evals"] == 3


def test_quorum_loss_triggers_flight_dump(tmp_path):
    """Regression: the minority-partition TimeoutError must leave a
    black box behind (previously untested)."""
    from paddle_tpu.distributed.resilience.supervisor import (
        Supervisor, SupervisorConfig)

    class _MinorityElastic:
        def host_map(self):
            return {0: "hostA", 1: "hostB", 2: "hostC"}

        def alive_members(self):
            return [0]               # only our own host heartbeats

    sup = Supervisor.__new__(Supervisor)
    sup.elastic = _MinorityElastic()
    sup.config = SupervisorConfig(host_id="hostA",
                                  reform_timeout_s=0.01,
                                  require_quorum=True)
    _tracing.set_flight_dir(str(tmp_path))
    lost0 = _metrics.counter("elastic/quorum_lost").value
    with pytest.raises(TimeoutError, match="quorum"):
        sup._check_quorum()
    assert _metrics.counter("elastic/quorum_lost").value - lost0 == 1
    dumps = [f for f in os.listdir(str(tmp_path))
             if "quorum_lost" in f]
    assert len(dumps) == 1
    with open(os.path.join(str(tmp_path), dumps[0])) as f:
        doc = json.load(f)
    assert doc["meta"]["host"] == "hostA"
    assert doc["meta"]["alive"] == ["hostA"]
    assert sorted(doc["meta"]["registered"]) == ["hostA", "hostB",
                                                 "hostC"]


# ---------------------------------------------------------------------------
# the acceptance run: SLO engine under the 4x gateway storm
# ---------------------------------------------------------------------------

def test_slo_engine_under_gateway_storm(model, tmp_path):
    """The ISSUE 16 acceptance criteria, end to end on a virtual step
    clock: attainment for all three classes, a fast-window burn alert
    raised during the storm and cleared (once — no flapping) after
    recovery, pre-storm windows embedded in a flight dump, and the
    advisor saying scale_up during the storm / hold after."""
    # bounded replica queues: the gateway must HOLD the storm backlog
    # (unbounded engine queues would swallow it before the ladder
    # climbs, and _shed_queued would find nothing to shed)
    gw, router = _fleet(model, GatewayConfig(
        classes=_classes(),
        tenants={"alpha": TenantConfig(rate=500.0, burst=100.0,
                                       weight=2.0),
                 "beta": TenantConfig(rate=500.0, burst=100.0)},
        brownout=BrownoutConfig(enter_load=1.0, exit_load=0.4,
                                hysteresis=2, clamp_max_new=4,
                                retry_after_s=0.25)), max_queue=6)
    step = [0]
    clock = lambda: float(step[0])     # noqa: E731
    tl = Timeline(clock=clock, spill_dir=str(tmp_path / "spill"))
    tracker = SLOTracker(
        class_objectives={"interactive": SLOObjective(target=0.999),
                          "batch": SLOObjective(target=0.99),
                          "best_effort": SLOObjective(target=0.99)},
        clock=clock, fast_window_s=40.0, slow_window_s=4000.0,
        burn_threshold=10.0, clear_after=3).attach(gw)
    advisor = ScaleAdvisor(tl, tracker, window_s=40.0, min_windows=3)
    _timeline.install(tl)
    tl.attach_flight(n=400)
    _tracing.set_flight_dir(str(tmp_path))

    def tick():
        step[0] += 1
        if step[0] % 5 == 0:
            tl.sample()
            tracker.evaluate()

    for _ in range(15):                       # pre-storm calm
        gw.step()
        tick()
    prestorm_seq = tl.windows()[-1]["seq"]
    assert tracker.evaluate() == []

    rng = np.random.RandomState(13)
    injected0 = _metrics.counter("gateway/storm_injected").value
    faults.arm("overload@admit%1.0:x=4")
    real = {}                  # ticket -> (prompt, stream_key, class)
    for tenant, slo, key0, n in (("alpha", "interactive", 1000, 6),
                                 ("beta", "batch", 2000, 4)):
        for key in range(key0, key0 + n):
            p = list(rng.randint(1, 90, 12))
            t = gw.submit(p, max_new_tokens=6, sampling=SP, tenant=tenant,
                          slo=slo, stream_key=key)
            real[t] = (p, key, slo)
    advice_during = None
    for _ in range(4000):
        gw.step()
        tick()
        if advice_during is None and gw.brownout.level >= 1 \
                and len(tl.windows()) >= 2:
            advice_during = advisor.recommend()
        if not gw.queued() and not router._live_pending():
            break
    faults.disarm()
    assert gw.brownout.max_level >= 1         # the storm engaged
    # each of the 10 arrivals became 4; no interactive request is lost
    # or cut short, and the ladder may shorten a batch stream but never
    # alter one: bitwise against an unloaded single engine under the
    # same pinned identity
    assert _metrics.counter("gateway/storm_injected").value \
        == injected0 + 3 * len(real)
    out = gw.results()
    for t, (p, key, slo) in real.items():
        ref = _unloaded_reference(model, p, key)
        toks = out.get(t) or []
        if slo == "interactive":
            assert toks == ref and len(toks) == 6
        else:
            assert toks == ref[:len(toks)]
    assert not [t for t in gw.timed_out() if t in real]
    storm_alerts = len(tracker.alerts)
    assert storm_alerts >= 1                  # fast-window burn paged
    assert any(a.tenant == "_storm" for a in tracker.alerts)
    assert advice_during is not None
    assert advice_during.action == "scale_up"

    # recovery: age the storm out of the fast window; capture the
    # advisory 20 steps after the clear (cleared edge still in horizon)
    cleared_at = None
    advice_after = None
    for _ in range(120):
        gw.step()
        tick()
        if cleared_at is None and not tracker.active_alerts():
            cleared_at = step[0]
        if advice_after is None and cleared_at is not None \
                and step[0] >= cleared_at + 20:
            advice_after = advisor.recommend()
    assert tracker.active_alerts() == []       # cleared...
    assert len(tracker.alerts) == storm_alerts  # ...without flapping
    assert all(a.cleared_t is not None for a in tracker.alerts)
    assert advice_after is not None
    assert advice_after.action == "hold"

    rep = tracker.report()
    assert set(rep["per_class"]) == {"interactive", "batch",
                                     "best_effort"}
    assert rep["per_class"]["interactive"]["attainment"] == 1.0
    assert rep["per_class"]["batch"]["attainment"] == 1.0
    assert rep["per_class"]["best_effort"]["attainment"] < 1.0
    assert rep["per_tenant"]["alpha/interactive"]["attainment"] == 1.0
    assert rep["per_tenant"]["_storm/best_effort"]["alert_active"] \
        is False

    # the black box carries the minutes BEFORE the incident
    path = _tracing.flight_dump("storm_postmortem")
    with open(path) as f:
        doc = json.load(f)
    assert any(w["seq"] <= prestorm_seq for w in doc["timeline"])
    # alert raise/clear both landed as timeline events
    kinds = {e["kind"] for e in tl.events()}
    assert "slo_alert" in kinds and "slo_alert_cleared" in kinds
    assert "gateway_brownout" in kinds
    # and the spill replays every window the manifest published
    replay = load_spill(str(tmp_path / "spill"))
    assert len(replay) == len(tl.windows())


def test_router_health_transitions_land_on_timeline(model):
    step = [0]
    tl = Timeline(clock=lambda: float(step[0]),
                  registry=_metrics.MetricsRegistry())
    _timeline.install(tl)
    _, router = _fleet(model)
    router.replicas[0].mark_unhealthy()
    router.replicas[0].probe()                 # half-open success #1
    router.replicas[0].probe()
    router.replicas[0].probe()                 # restore_after reached
    tl.sample()
    kinds = [e["kind"] for e in tl.events()]
    assert "replica_demoted" in kinds
    assert "replica_restored" in kinds
