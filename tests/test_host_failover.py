"""Host-level fault domains (ISSUE 10): replicated rendezvous store,
partition-fenced elastic recovery, and cross-host serving failover.

Coverage map:

- Store replication plane: a hot-standby tails the primary's mutations
  over the CRC/ACK record framing; killing the primary's server (every
  connection severed, like a host death) makes the ``FailoverStore``
  client redial the standby and keep answering — ``store/failovers`` /
  ``store/standby_takeovers`` record the event.
- Generation fencing: a write carrying a stale generation for its
  domain is refused with ``StaleGenerationError`` and counted in
  ``elastic/fenced_writes`` — on the primary AND on the standby after a
  takeover (the fence itself replicates).
- ElasticManager heartbeats ride the failover client: membership
  (``dead_members`` / ``wait_for_members``) stays correct across a
  store-primary death.
- Host-aware snapshot ring: with a balanced 2-host x 2-rank map every
  ring neighbor is off-host, so a whole-host loss never takes a state
  and its only replica together.
- Quorum gate: a rank seeing only a minority of registered hosts alive
  refuses to re-form (``elastic/quorum_lost``) instead of forming a
  splinter group.
- Fault DSL: ``kill@host`` / ``partition@dial`` parse and validate;
  frame-level kinds at process sites are rejected; a felled host is
  sticky in the injector.
- Serving: drain targets order off-host first, cross-host hand-offs
  ride a caller-supplied transport pair, and a ``kill@host`` plan fells
  every co-hosted replica with zero lost requests and bitwise-identical
  streams.
- The acceptance chaos run — a 4-rank, 2-host ``run_elastic`` where
  host B is felled mid-run and both its ranks rejoin — lives in the
  module-scoped ``host_cluster`` fixture below (subprocesses, mirroring
  test_resilience.py's 2-rank harness).
"""
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.elastic import ElasticManager
from paddle_tpu.distributed.resilience import faults
from paddle_tpu.distributed.resilience.errors import (
    StaleGenerationError, StoreTimeoutError, TransportError)
from paddle_tpu.distributed.resilience.supervisor import (
    Supervisor, SupervisorConfig, host_aware_ring)
from paddle_tpu.distributed.store import (FailoverStore, StandbyStore,
                                          TCPStore, connect_store)
from paddle_tpu.profiler import metrics


def _cval(name):
    return metrics.counter(name).value


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.disarm()


# ---------------------------------------------------------------------------
# store replication + client failover
# ---------------------------------------------------------------------------

@pytest.fixture()
def store_pair():
    primary = TCPStore("127.0.0.1", 0, is_master=True)
    standby = StandbyStore("127.0.0.1", primary.port)
    yield primary, standby
    standby.close()
    primary.close()


def test_standby_tails_primary_mutations(store_pair):
    primary, standby = store_pair
    c0 = _cval("store/replicated_records")
    primary.set("alpha", b"1")
    primary.add("ctr", 5)
    primary.set("beta", b"2")
    primary.delete_key("beta")
    # replication is applied under the server's condition before the op
    # acks, so a read-your-write through the standby is deterministic
    probe = TCPStore("127.0.0.1", standby.port)
    try:
        assert probe.get_nowait("alpha") == b"1"
        assert probe.get_nowait("ctr") == b"5"
        with pytest.raises(KeyError):
            probe.get_nowait("beta")
    finally:
        probe.close()
    assert _cval("store/replicated_records") >= c0 + 4


def test_standby_receives_snapshot_of_pre_dial_state():
    primary = TCPStore("127.0.0.1", 0, is_master=True)
    try:
        primary.set("early", b"yes")        # written BEFORE the standby
        standby = StandbyStore("127.0.0.1", primary.port)
        try:
            probe = TCPStore("127.0.0.1", standby.port)
            try:
                assert probe.get_nowait("early") == b"yes"
            finally:
                probe.close()
        finally:
            standby.close()
    finally:
        primary.close()


def test_failover_client_redials_standby_on_primary_death(store_pair):
    primary, standby = store_pair
    client = FailoverStore([(primary.host, primary.port),
                            (standby.host, standby.port)], rank=0)
    try:
        client.set("k", b"v")
        f0 = _cval("store/failovers")
        t0 = _cval("store/standby_takeovers")
        primary._server.stop()              # host death: every conn cut
        assert client.get("k") == b"v"      # answered by the standby
        client.set("post", b"takeover")     # standby accepts writes too
        assert client.add("ctr2", 3) == 3
        assert client.get("post") == b"takeover"
        assert _cval("store/failovers") >= f0 + 1
        deadline = time.time() + 5
        while _cval("store/standby_takeovers") < t0 + 1 \
                and time.time() < deadline:
            time.sleep(0.05)
        assert _cval("store/standby_takeovers") >= t0 + 1
        assert standby.primary_alive is False
    finally:
        client.close()


def test_connect_store_appends_env_standby_endpoints(store_pair, monkeypatch):
    primary, standby = store_pair
    monkeypatch.setenv("PT_STORE_STANDBY",
                       f"{standby.host}:{standby.port}")
    client = connect_store(primary.host, primary.port, rank=1)
    try:
        assert (standby.host, standby.port) in client.endpoints
        client.set("via_env", b"1")
        primary._server.stop()
        assert client.get("via_env") == b"1"
    finally:
        client.close()


def test_store_timeout_is_structured():
    primary = TCPStore("127.0.0.1", 0, is_master=True)
    client = TCPStore("127.0.0.1", primary.port, timeout=0.3)
    try:
        with pytest.raises(StoreTimeoutError) as ei:
            client.get("never-set")
        err = ei.value
        assert err.key == "never-set"
        assert err.endpoint == client.endpoint
        assert err.timeout_s == 0.3
        assert isinstance(err, TimeoutError)      # recoverable upstream
        assert isinstance(err, TransportError)
        with pytest.raises(StoreTimeoutError) as ei2:
            client.wait(["also-never"], timeout=0.2)
        assert ei2.value.op == "wait"
    finally:
        client.close()
        primary.close()


# ---------------------------------------------------------------------------
# generation fencing
# ---------------------------------------------------------------------------

def test_fenced_write_refused_with_stale_generation(store_pair):
    primary, _ = store_pair
    c0 = _cval("elastic/fenced_writes")
    primary.fenced_set("reg/0", b"a", domain="sup/j", gen=3)
    primary.fenced_set("reg/1", b"b", domain="sup/j", gen=3)   # same gen ok
    primary.fenced_set("reg/0", b"c", domain="sup/j", gen=4)   # advance ok
    with pytest.raises(StaleGenerationError) as ei:
        primary.fenced_set("reg/1", b"stale", domain="sup/j", gen=2)
    err = ei.value
    assert err.write_gen == 2 and err.fence_gen == 4
    assert err.domain == "sup/j"
    # the refused write changed nothing
    assert primary.get_nowait("reg/1") == b"b"
    assert _cval("elastic/fenced_writes") == c0 + 1
    # an unrelated domain has its own fence
    primary.fenced_set("reg/9", b"x", domain="sup/other", gen=0)


def test_fence_survives_standby_takeover(store_pair):
    primary, standby = store_pair
    client = FailoverStore([(primary.host, primary.port),
                            (standby.host, standby.port)], rank=2)
    try:
        client.fenced_set("g/reg", b"new", domain="d1", gen=7)
        primary._server.stop()
        # the fence high-water mark replicated with the data: a
        # minority-partition rank writing through the standby with its
        # stale generation is refused there too
        with pytest.raises(StaleGenerationError):
            client.fenced_set("g/reg", b"old", domain="d1", gen=6)
        assert client.get("g/reg") == b"new"
        client.fenced_set("g/reg", b"next", domain="d1", gen=8)
    finally:
        client.close()


# ---------------------------------------------------------------------------
# elastic membership across store failover
# ---------------------------------------------------------------------------

def test_elastic_membership_survives_store_failover(store_pair):
    primary, standby = store_pair
    c0 = TCPStore("127.0.0.1", primary.port)
    mgr_keys_seeded = ElasticManager(c0, "jobF", rank=1, min_nodes=2,
                                     max_nodes=2, host_id="hostB")
    mgr_keys_seeded.register()
    client = FailoverStore([(primary.host, primary.port),
                            (standby.host, standby.port)], rank=0)
    mgr = ElasticManager(client, "jobF", rank=0, min_nodes=2,
                         max_nodes=2, ttl=2.0, host_id="hostA")
    try:
        mgr.register()
        assert sorted(mgr.alive_members()) == [0, 1]
        assert mgr.host_map() == {0: "hostA", 1: "hostB"}
        assert mgr.alive_hosts() == ["hostA", "hostB"]
        assert mgr.wait_for_members(2, timeout=5) == [0, 1]
        primary._server.stop()              # store host dies
        mgr._beat_once()                    # heartbeat rides the standby
        assert mgr.heartbeat_errors == 0
        assert 0 in mgr.alive_members()
        # rank 1 dies with the store host: its (replicated) beat goes
        # stale and it shows up dead THROUGH THE STANDBY, relative to
        # the last-known membership
        client.set("jobF/hb/1", str(time.time() - 100))
        assert mgr.dead_members() == [1]
        with pytest.raises(TimeoutError):
            mgr.wait_for_members(2, timeout=0.5)
        # and a rejoin (fresh beat via the standby) re-forms the set
        client.set("jobF/hb/1", str(time.time()))
        assert mgr.wait_for_members(2, timeout=5) == [0, 1]
    finally:
        mgr.stop()
        client.close()
        c0.close()


# ---------------------------------------------------------------------------
# host-aware ring + quorum gate
# ---------------------------------------------------------------------------

def test_host_aware_ring_neighbors_off_host_2x2():
    ring = host_aware_ring({0: "hA", 1: "hA", 2: "hB", 3: "hB"})
    assert sorted(ring) == [0, 1, 2, 3]
    hosts = {0: "hA", 1: "hA", 2: "hB", 3: "hB"}
    for i, r in enumerate(ring):
        nxt = ring[(i + 1) % len(ring)]
        assert hosts[r] != hosts[nxt], \
            f"ring {ring}: neighbor {r}->{nxt} shares host {hosts[r]}"


def test_host_aware_ring_unbalanced_and_trivial():
    # 3 ranks on hA, 1 on hB: interleaving still alternates while hB
    # has ranks to give; a single-host map degrades to rank order
    ring = host_aware_ring({0: "hA", 1: "hA", 2: "hA", 3: "hB"})
    assert sorted(ring) == [0, 1, 2, 3]
    assert host_aware_ring({0: "h", 1: "h"}) == [0, 1]
    assert host_aware_ring({}) == []


def _quorum_cfg(**over):
    kw = dict(rank=0, world_size=2, job_id=f"q{os.getpid()}",
              host_id="hA", reform_timeout_s=1.0,
              watchdog_timeout_s=0.0, heartbeat_ttl_s=2.0)
    kw.update(over)
    return SupervisorConfig(**kw)


def test_quorum_gate_blocks_minority_then_admits():
    master = TCPStore("127.0.0.1", 0, is_master=True)
    client = TCPStore("127.0.0.1", master.port)
    sup = Supervisor(_quorum_cfg(), store=client)
    try:
        job = sup.elastic.job_id
        # a second REGISTERED host whose heartbeat is long stale: one of
        # two hosts alive is NOT a strict majority
        master.set(f"{job}/host/1", "hB")
        master.set(f"{job}/hb/1", str(time.time() - 100))
        lost0 = _cval("elastic/quorum_lost")
        with pytest.raises(TimeoutError, match="quorum"):
            sup._check_quorum()
        assert _cval("elastic/quorum_lost") == lost0 + 1
        # the host comes back (relaunched ranks re-register heartbeats):
        # the same gate now passes
        master.set(f"{job}/hb/1", str(time.time()))
        ok0 = _cval("elastic/quorum_ok")
        sup._check_quorum()
        assert _cval("elastic/quorum_ok") == ok0 + 1
    finally:
        sup.elastic.stop()
        client.close()
        master.close()


def test_quorum_gate_opt_out_and_single_host():
    master = TCPStore("127.0.0.1", 0, is_master=True)
    client = TCPStore("127.0.0.1", master.port)
    sup = Supervisor(_quorum_cfg(require_quorum=False), store=client)
    try:
        master.set(f"{sup.elastic.job_id}/host/1", "hB")
        master.set(f"{sup.elastic.job_id}/hb/1", str(time.time() - 100))
        sup._check_quorum()                 # opt-out: no gate
    finally:
        sup.elastic.stop()
        client.close()
        master.close()
    # all ranks on one host: the gate is trivially satisfied
    master = TCPStore("127.0.0.1", 0, is_master=True)
    client = TCPStore("127.0.0.1", master.port)
    sup = Supervisor(_quorum_cfg(), store=client)
    try:
        sup._check_quorum()
    finally:
        sup.elastic.stop()
        client.close()
        master.close()


# ---------------------------------------------------------------------------
# fault DSL: host site, partition kind, sticky felled hosts
# ---------------------------------------------------------------------------

def test_plan_accepts_host_kill_and_dial_partition():
    p = faults.parse_plan("kill@host#1:host=h1,partition@dial#2:rank=1")
    assert [r.kind for r in p.rules] == ["kill", "partition"]
    assert p.rules[0].site == "host" and p.rules[0].host == "h1"
    assert p.rules[1].site == "dial"
    assert "host=h1" in p.describe()


@pytest.mark.parametrize("bad", [
    "drop@host#1",            # frame kind at a process site
    "corrupt@host#1:host=h1",
    "dup@step#1",
    "partition@send#1",       # partition only severs dials
    "partition@host#1",
])
def test_plan_rejects_invalid_site_kind_pairs(bad):
    with pytest.raises(ValueError):
        faults.parse_plan(bad)


def test_felled_host_is_sticky_across_corank_events():
    faults.arm("kill@host#2:host=hB")
    inj = faults.injector
    assert inj.on_event("host", 0, host="hA") is None
    act = None
    # hB's second host-site event trips the rule...
    for _ in range(2):
        act = inj.on_event("host", 2, host="hB")
    assert act is not None and act.kind == "kill"
    assert "hB" in inj.felled_hosts()
    # ...and every LATER event from any rank sharing hB is killed
    # without consuming more rule budget (the host is down)
    act2 = inj.on_event("host", 3, host="hB")
    assert act2 is not None and act2.kind == "kill"
    assert inj.on_event("host", 0, host="hA") is None
    faults.disarm()
    assert faults.injector.felled_hosts() == set() \
        or not faults.injector.felled_hosts()


# ---------------------------------------------------------------------------
# cross-host serving failover
# ---------------------------------------------------------------------------

class _FakeEngine:
    """Just enough surface for Replica health/load scoring."""

    class _Cfg:
        max_batch = 4
        num_blocks = 9

    def __init__(self, n_pending=0):
        self.cfg = self._Cfg()
        self._pending = [None] * n_pending
        self._free_pages = list(range(8))
        self.requeue_hook = None

    def pending(self):
        return self._pending


def test_drain_ordering_prefers_off_host_peers():
    from paddle_tpu.inference.router import Replica, ReplicaRouter

    router = ReplicaRouter([
        Replica(_FakeEngine(3), name="r0", host_id="h0"),  # busy, off-host
        Replica(_FakeEngine(0), name="r1", host_id="h1"),  # idle, co-host
        Replica(_FakeEngine(1), name="r2", host_id="h0"),  # off-host
        Replica(_FakeEngine(0), name="r3", host_id="h1"),  # dying
    ])
    order = router._ordered(exclude=3, prefer_off_host="h1")
    # every h0 replica (even the busy one) outranks the co-host peer
    assert order == [2, 0, 1]
    # without the hint, pure load order
    assert router._ordered(exclude=3) == [1, 2, 0]
    # replicas without a host label count as off-host (unknown domain)
    router.replicas[0].host_id = None
    assert router._ordered(exclude=3, prefer_off_host="h1")[-1] == 1


_SRV = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
            num_kv_heads=2, ffn_size=64, block_size=8, num_blocks=48,
            max_batch=3, max_blocks_per_seq=6, token_budget=32)


@pytest.fixture(scope="module")
def srv_model():
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig)
    paddle.seed(5)
    m = PagedCausalLM(PagedServingConfig(**_SRV))
    m.eval()
    return m


def _host_fleet(srv_model, handoff_factory=None):
    from paddle_tpu.inference.fleet_supervisor import (
        FleetSupervisor, FleetSupervisorConfig)
    from paddle_tpu.inference.router import Replica, ReplicaRouter
    from paddle_tpu.inference.serving import (PagedServingConfig,
                                              ServingEngine)

    hosts = ("h0", "h0", "h1", "h1")

    def factory(idx):
        eng = ServingEngine.from_model(
            srv_model, PagedServingConfig(**_SRV), seed=10 + idx)
        eng.fault_rank = idx
        eng.host_id = "h0"      # restarts land on the surviving host
        return eng

    engines = []
    for i in range(4):
        e = ServingEngine.from_model(
            srv_model, PagedServingConfig(**_SRV), seed=10 + i)
        e.fault_rank = i
        e.host_id = hosts[i]
        engines.append(e)
    router = ReplicaRouter([Replica(e, name=f"r{i}", restore_after=2)
                            for i, e in enumerate(engines)])
    sup = FleetSupervisor(router, engine_factory=factory,
                          cfg=FleetSupervisorConfig(backoff_base_s=0.0),
                          handoff_factory=handoff_factory)
    return router, sup


def _wave(router, max_new=6):
    from paddle_tpu.inference.serving import SamplingParams

    rng = np.random.RandomState(41)
    sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.95)
    return [router.submit(list(rng.randint(1, 90, n)),
                          max_new_tokens=max_new, sampling=sp)
            for n in (9, 11, 7, 13, 8, 10)]


def test_host_kill_fells_cohosted_replicas_zero_loss(srv_model):
    """kill@host fells BOTH h1 replicas; every in-flight request drains
    to the surviving h0 pair and every stream stays bitwise-identical
    to an uninterrupted run."""
    faults.disarm()
    router, _ = _host_fleet(srv_model)
    hs = _wave(router)
    ref = router.run_to_completion()
    ref = {h: ref[h] for h in hs}

    c_drain0 = _cval("serving/cross_host_drains")
    faults.arm("kill@host#2:host=h1")
    router, sup = _host_fleet(srv_model)
    hs = _wave(router)
    out = router.run_to_completion()
    faults.disarm()
    out = {h: out[h] for h in hs}

    assert out == ref
    assert all(len(toks) == 6 for toks in out.values())  # none cut short
    assert not router.timed_out()
    # both h1 slots burned a restart and came back on h0
    assert sup.restarts[2] == 1 and sup.restarts[3] == 1
    assert router.replicas[2].host_id == "h0"
    assert router.replicas[3].host_id == "h0"
    assert _cval("serving/cross_host_drains") > c_drain0


def test_handoff_factory_carries_cross_host_migration(srv_model):
    """A caller-supplied transport pair (the cross-host TensorTransport
    seam) carries the KV hand-off; the supervisor asks for one per
    migration instead of assuming in-process loopback."""
    from paddle_tpu.inference.fleet_supervisor import LoopbackTransport

    calls = []

    def handoff(src_idx, dst_idx):
        tp = LoopbackTransport()       # stands in for a real transport
        calls.append((src_idx, dst_idx))
        return tp, tp, 1, 0

    faults.disarm()
    router, sup = _host_fleet(srv_model, handoff_factory=handoff)
    hs = _wave(router)
    c_mig0 = _cval("serving/cross_host_migrations")
    # decode every request to its tip, then fell one h1 replica: the
    # drain takes the migration path through the factory's transport
    # (two calls: the second fetches the prefill's tokens. What it
    # dispatched itself is dropped with the engine, and sampled again
    # under the same salt where each request lands)
    router.step_all()
    router.step_all()
    victim = 2
    router.replicas[victim].engine.dead = True
    recovered = sup.pump()
    assert victim in recovered
    out = router.run_to_completion()
    out = {h: out[h] for h in hs}
    assert not router.timed_out()
    assert all(len(v) == 6 for v in out.values())
    # the victim had decode-tip requests: at least one rode the
    # factory's transport, and the hand-off crossed hosts
    assert calls
    assert all(src == victim for src, _dst in calls)
    assert _cval("serving/cross_host_migrations") > c_mig0


def test_partition_at_dial_blocks_failover_redial(store_pair):
    """A partitioned client cannot reach ANY endpoint: the redial sweep
    keeps consulting the dial site and ultimately surfaces
    ConnectionError instead of hanging."""
    primary, standby = store_pair
    client = FailoverStore([(primary.host, primary.port),
                            (standby.host, standby.port)],
                           rank=5, timeout=3.0)
    try:
        client.set("pk", b"1")
        faults.arm("partition@dial%1.0:rank=5")
        primary._server.stop()
        with pytest.raises((ConnectionError, OSError)):
            client.get("pk")
        faults.disarm()
        # partition healed: the next op redials the standby and answers
        assert client.get("pk") == b"1"
    finally:
        faults.disarm()
        client.close()


# ---------------------------------------------------------------------------
# acceptance chaos run: 4-rank / 2-host elastic training, host B felled
# ---------------------------------------------------------------------------

_HOSTS4 = ("hostA", "hostA", "hostB", "hostB")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _host_env(out_dir, port, standby_port, rank, rejoin=False):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PADDLE_JAX_DISTRIBUTED": "0",
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": "4",
        "PADDLE_TRAINER_ENDPOINTS": ",".join(
            f"127.0.0.1:618{r}" for r in range(4)),
        "PADDLE_CURRENT_ENDPOINT": f"127.0.0.1:618{rank}",
        "PADDLE_MASTER": f"127.0.0.1:{port}",
        "PADDLE_STORE_TIMEOUT": "120",
        "RESILIENCE_MODE": "elastic",
        "RESILIENCE_OUT_DIR": out_dir,
        "PT_HOST_ID": _HOSTS4[rank],
        # a passive hot-standby store rides along on rank 1 (hostA):
        # exercises the deployment wiring inside a real cluster
        "PT_STORE_STANDBY": f"127.0.0.1:{standby_port}",
        "PT_STORE_STANDBY_RANK": "1",
        "WATCHDOG_TIMEOUT": "3",
        "REFORM_TIMEOUT": "120",
    })
    env.pop("XLA_FLAGS", None)
    env.pop("PT_FAULT_PLAN", None)
    env.pop("PT_SUPERVISOR_REJOIN", None)
    env.pop("TOY_NAN_STEP", None)
    if rejoin:
        env["PT_SUPERVISOR_REJOIN"] = "1"
    elif _HOSTS4[rank] == "hostB":
        # hostB dies at its ranks' 5th host-site consult (= start of
        # step index 4) — BOTH co-hosted ranks fall, same failure domain
        env["PT_FAULT_PLAN"] = "kill@host#5:host=hostB"
    return env


def _run_host_cluster(out_dir, timeout=240):
    """Spawn the 4-rank run, let the plan fell hostB (ranks 2 AND 3),
    relaunch both as rejoiners (the launch controller's job, played by
    the test), and collect all four ranks' outputs."""
    worker = os.path.join(os.path.dirname(__file__),
                          "resilience_worker.py")
    port = _free_port()
    standby_port = _free_port()

    def spawn(rank, rejoin=False):
        return subprocess.Popen(
            [sys.executable, worker],
            env=_host_env(out_dir, port, standby_port, rank,
                          rejoin=rejoin),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

    procs = {r: spawn(r) for r in range(4)}
    try:
        for r in (2, 3):
            rc = procs[r].wait(timeout=timeout)
            assert rc != 0, f"fault plan should have killed rank {r}"
        rejoiners = {r: spawn(r, rejoin=True) for r in (2, 3)}
        outs, rcs = {}, {}
        for r in (0, 1):
            out, _ = procs[r].communicate(timeout=timeout)
            outs[r], rcs[r] = out.decode(), procs[r].returncode
        for r in (2, 3):
            out, _ = rejoiners[r].communicate(timeout=timeout)
            outs[r], rcs[r] = out.decode(), rejoiners[r].returncode
        return rcs, outs
    finally:
        for p in list(procs.values()) + list(
                locals().get("rejoiners", {}).values()):
            if p.poll() is None:
                p.kill()


@pytest.fixture(scope="module")
def host_cluster(tmp_path_factory):
    last = None
    for attempt in range(3):
        out_dir = str(tmp_path_factory.mktemp(f"hostloss{attempt}"))
        rcs, outs = _run_host_cluster(out_dir)
        if all(rc == 0 for rc in rcs.values()):
            data = {}
            for r in range(4):
                npz = dict(np.load(os.path.join(out_dir, f"rank{r}.npz"),
                                   allow_pickle=True))
                data[r] = {
                    "w": npz["w"], "losses": npz["losses"],
                    "report": json.loads(str(npz["report"])),
                    "metrics": json.loads(str(npz["metrics"])),
                }
            return data
        last = (rcs, outs)
    pytest.fail(
        f"host-loss cluster failed after retries: rc={last[0]}\n"
        + "\n".join(f"--- rank{r} ---\n{o}"
                    for r, o in sorted(last[1].items())))


def test_host_loss_reforms_with_quorum(host_cluster):
    """hostB's two ranks die together; the survivors gate the re-form
    on host quorum (waiting for the relaunch), and all four ranks
    finish every step."""
    import resilience_worker as rw

    for r in range(4):
        rep = host_cluster[r]["report"]
        assert rep["final_step"] == rw.TOY_STEPS, (r, rep)
    # survivors burned exactly one restart each (within max_restarts=1)
    assert host_cluster[0]["report"]["restarts"] == 1
    assert host_cluster[1]["report"]["restarts"] == 1
    # the quorum gate ran and passed on the surviving host
    for r in (0, 1):
        m = host_cluster[r]["metrics"]
        assert m.get("elastic/quorum_checks", 0) >= 1, m
        assert m.get("elastic/quorum_ok", 0) >= 1, m


def test_host_loss_rejoiners_restore_off_host(host_cluster):
    """With the host-aware ring, each hostB rank's snapshot lived on a
    hostA neighbor — the rejoiners restore from a PEER replica (or the
    disk tier), never from state that died with their own host."""
    for r in (2, 3):
        rep = host_cluster[r]["report"]
        srcs = [s for _, s in rep["recovery_sources"]]
        assert srcs, rep
        assert set(srcs) <= {"peer", "disk"}, rep
        # the state restored is the step-4 snapshot (snapshot_every=2,
        # felled at the start of step 4)
        assert rep["recovery_sources"][0][0] == 4, rep
    # the rejoined processes did not re-fire the plan
    for r in (2, 3):
        assert host_cluster[r]["metrics"].get("faults/injected", 0) == 0


def test_host_loss_final_loss_bitwise_parity(host_cluster):
    """The healed 4-rank run lands on weights and losses bitwise-equal
    to an uninterrupted 4-rank reference."""
    import resilience_worker as rw

    w_ref, losses_ref = rw.toy_reference(world=4)
    for r in range(4):
        np.testing.assert_array_equal(
            host_cluster[r]["w"], w_ref,
            err_msg=f"rank {r} final weights diverged")
    # rank 0 holds the full trajectory; rejoiners from the restored
    # step onward
    np.testing.assert_array_equal(host_cluster[0]["losses"],
                                  np.asarray(losses_ref))
    for r in (2, 3):
        lr = host_cluster[r]["losses"]
        np.testing.assert_array_equal(
            lr[4:], np.asarray(losses_ref)[4:])
