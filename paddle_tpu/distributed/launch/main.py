"""Launch CLI: `python -m paddle_tpu.distributed.launch [...] train.py`.

Reference analog: python/paddle/distributed/launch/main.py:21 + controllers
(controller.py:79,192 run/build_pod, collective.py:37, master.py rendezvous,
watcher.py) and the elastic manager (fleet/elastic/manager.py:124).

TPU-native shape: ONE worker process per HOST (single-controller JAX drives
all local chips), not one per device. Rendezvous uses the launcher TCPStore
(distributed/store.py); each worker gets the reference env contract
(PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM / PADDLE_TRAINER_ENDPOINTS /
PADDLE_CURRENT_ENDPOINT) so fleet.init works unchanged. A watch loop
restarts failed workers up to --max_restart times; elastic mode re-forms
the job when membership changes (heartbeat keys with TTL in the store).
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
from typing import List, Optional

__all__ = ["launch", "main"]


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def parse_args(argv=None):
    parser = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    parser.add_argument("--master", default=None,
                        help="host:port of the rendezvous store "
                             "(default: local)")
    parser.add_argument("--nnodes", default="1",
                        help="node count, or lo:hi range for elastic")
    parser.add_argument("--rank", type=int, default=-1,
                        help="node rank (default: assigned by the store)")
    parser.add_argument("--nproc_per_node", type=int, default=1,
                        help="worker processes per node (1 = "
                             "single-controller over all local chips; "
                             "more than 1 is refused on a TPU host "
                             "unless JAX_PLATFORMS keeps workers off "
                             "the chips)")
    parser.add_argument("--devices", "--gpus", "--xpus", default=None,
                        help="accepted for reference compat; TPU chips are "
                             "addressed by the controller process")
    parser.add_argument("--job_id", default="default")
    parser.add_argument("--log_dir", default="log")
    parser.add_argument("--max_restart", type=int, default=3)
    parser.add_argument("--ckpt_dir", default=None,
                        help="checkpoint root for the elastic "
                             "supervisor's disk tier (PT_CKPT_ROOT)")
    parser.add_argument("--standby", default=None,
                        help="host:port of the hot-standby rendezvous "
                             "store replica (PT_STORE_STANDBY); a "
                             "non-master controller matching the host "
                             "serves it, every store client fails over "
                             "to it when the primary's host dies")
    parser.add_argument("--snapshot_every", type=int, default=0,
                        help="in-memory replicated snapshot interval "
                             "in steps for supervised workers "
                             "(PT_SNAPSHOT_EVERY; 0 = leave unset)")
    parser.add_argument("--elastic_timeout", type=float, default=30.0)
    parser.add_argument("--elastic_ttl", type=float, default=10.0,
                        help="heartbeat staleness after which a peer node "
                             "is considered gone (elastic mode)")
    parser.add_argument("--host", default=None)
    parser.add_argument("training_script")
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return parser.parse_args(argv)


def _local_tpu_chips() -> int:
    """TPU chips attached to this host, counted from their device nodes
    (/dev/accel<n>, or /dev/vfio/<n> as the v5e host has them): the
    launcher parent never imports JAX (a process that touches JAX takes
    the chip from its workers). A vfio node is any passed-through device,
    so this can count what is no TPU; the refusal says how it counted."""
    import glob

    return len(glob.glob("/dev/accel[0-9]*")) \
        or len(glob.glob("/dev/vfio/[0-9]*"))


def check_one_process_per_chip(nproc_per_node: int, env) -> None:
    """A chip belongs to one process at a time. Several local workers on
    a chip host would all open every local chip and fight for them, so
    the launcher refuses — unless the workers are told to stay off the
    chip (JAX_PLATFORMS without "tpu", as the CPU test suite sets)."""
    platforms = env.get("JAX_PLATFORMS", "")
    if nproc_per_node <= 1:
        return
    if platforms and "tpu" not in platforms.lower().split(","):
        return
    chips = _local_tpu_chips()
    if chips:
        raise SystemExit(
            f"[launch] refusing --nproc_per_node {nproc_per_node} on a "
            f"host with {chips} TPU chip(s) (device nodes /dev/accel<n> "
            f"or /dev/vfio/<n>; JAX is not asked): every worker would "
            f"open all local chips, and a chip belongs to one process at "
            f"a time. Run one worker per host (--nproc_per_node 1; one "
            f"controller process drives all local chips). If the workers "
            f"are to run on the CPU, or those nodes are no TPU chips, say "
            f"where they run: JAX_PLATFORMS=cpu.")


class Pod:
    def __init__(self, rank: int, world: List[str], local_procs: int):
        self.rank = rank
        self.world = world
        self.local_procs = local_procs
        self.procs: List[subprocess.Popen] = []


class Controller:
    """reference controller.py:79 — build job, spawn workers, watch."""

    def __init__(self, args):
        self.args = args
        self.host = args.host or socket.gethostbyname(socket.gethostname())
        lo, _, hi = args.nnodes.partition(":")
        self.min_nodes = int(lo)
        self.max_nodes = int(hi) if hi else self.min_nodes
        self.elastic = bool(hi)
        self.store = None
        self.standby = None
        self.is_master = False
        self.generation = 0
        self._missing_since = {}      # (gen, rank) -> first-seen-missing
        self._worker_failures = 0     # non-elastic exit codes, cumulative

    # -- rendezvous --------------------------------------------------------
    def _connect_store(self):
        from ..store import connect_store

        standby = self.args.standby \
            or os.environ.get("PT_STORE_STANDBY") or None
        if self.args.master is None:
            port = _free_port()
            self.store = connect_store("127.0.0.1", port, is_master=True,
                                       standby=standby or "")
            self.is_master = True
        else:
            host, _, port = self.args.master.partition(":")
            want_master = self.args.rank in (-1, 0)
            try:
                self.store = connect_store(host, int(port),
                                           is_master=False, timeout=5.0,
                                           standby=standby or "")
            except ConnectionError:
                try:
                    self.store = connect_store(host, int(port),
                                               is_master=True,
                                               standby=standby or "")
                    self.is_master = True
                except OSError:
                    # lost the hosting race (EADDRINUSE): a peer
                    # controller bound the port between our probe and
                    # our bind — join it as a client, patiently
                    self.store = connect_store(host, int(port),
                                               is_master=False,
                                               timeout=30.0,
                                               standby=standby or "")
        self._maybe_host_standby(standby)

    def _maybe_host_standby(self, standby: Optional[str]):
        """Serve the hot-standby replica when --standby names an
        endpoint this controller should bind: a NON-master controller
        whose host matches (the off-host deployment), or the local
        single-controller case (dev convenience). EADDRINUSE means a
        peer already serves it — fine."""
        if not standby:
            return
        host, _, port = standby.partition(":")
        local = host in ("127.0.0.1", "localhost", self.host)
        if not local or (self.is_master and self.args.master is not None):
            return
        from ..store import StandbyStore

        primary = self.store.endpoints[0]
        try:
            self.standby = StandbyStore(primary[0], primary[1],
                                        host=host, port=int(port),
                                        timeout=30.0)
        except (ConnectionError, OSError) as e:
            print(f"[launch] standby store at {standby} not started: "
                  f"{e!r}", file=sys.stderr)

    def _ns(self):
        return f"{self.args.job_id}/g{self.generation}"

    def build_pod(self) -> Pod:
        if self.store is None:
            self._connect_store()
        if self.max_nodes <= 1 and self.args.master is None:
            return Pod(0, [f"{self.host}:{_free_port()}"],
                       self.args.nproc_per_node)
        if self.elastic:
            self.generation = self.store.add(
                f"{self.args.job_id}/gen_bump", 0)
        # register this node, allgather endpoints through the store;
        # keys are generation-namespaced so elastic re-formation gets a
        # fresh rendezvous with remapped ranks (reference
        # fleet/elastic/manager.py:124-277 rank re-map on rescale)
        endpoint = f"{self.host}:{_free_port()}"
        rank = self.args.rank
        if rank < 0 or self.elastic:
            rank = self.store.add(f"{self._ns()}/nodes", 1) - 1
        self.store.set(f"{self._ns()}/ep/{rank}", endpoint)
        if self.elastic:
            # wait for membership to settle within [min, max]
            deadline = time.time() + self.args.elastic_timeout
            last_n, stable_since = 0, time.time()
            while True:
                bump = self.store.add(f"{self.args.job_id}/gen_bump", 0)
                if bump > self.generation:
                    # someone re-triggered mid-rendezvous: move up
                    self.generation = bump
                    rank = self.store.add(f"{self._ns()}/nodes", 1) - 1
                    self.store.set(f"{self._ns()}/ep/{rank}", endpoint)
                    last_n, stable_since = 0, time.time()
                n = self.store.add(f"{self._ns()}/nodes", 0)
                if n != last_n:
                    last_n, stable_since = n, time.time()
                if n >= self.min_nodes                         and time.time() - stable_since >= 1.0:
                    break
                if time.time() > deadline:
                    if n >= self.min_nodes:
                        break
                    raise RuntimeError(
                        f"elastic rendezvous timeout: {n} nodes < "
                        f"min {self.min_nodes}")
                time.sleep(0.2)
            world_n = min(last_n, self.max_nodes)
            if rank >= world_n:
                # pod is full: stand by as a spare until it re-forms
                # (a member death bumps the generation; we then rejoin)
                print(f"[launch] node rank {rank} standing by (pod full "
                      f"at {world_n})", file=sys.stderr)
                cur = self.store.add(f"{self.args.job_id}/gen_bump", 0)
                while self.store.add(f"{self.args.job_id}/gen_bump",
                                     0) == cur:
                    time.sleep(1.0)
                self.generation = self.store.add(
                    f"{self.args.job_id}/gen_bump", 0)
                return self.build_pod()
        else:
            world_n = self.min_nodes
        world = []
        for r in range(world_n):
            world.append(self.store.get(
                f"{self._ns()}/ep/{r}").decode())
        self._heartbeat_now(rank)
        return Pod(rank, world, self.args.nproc_per_node)

    # -- spawn -------------------------------------------------------------
    def _worker_env(self, pod: Pod, local_idx: int):
        env = dict(os.environ)
        n_world = len(pod.world) * pod.local_procs
        global_rank = pod.rank * pod.local_procs + local_idx
        env.update({
            "PADDLE_TRAINER_ID": str(global_rank),
            "PADDLE_TRAINERS_NUM": str(n_world),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(pod.world),
            "PADDLE_CURRENT_ENDPOINT": pod.world[pod.rank],
            "PADDLE_JOB_ID": self.args.job_id,
            "PADDLE_MASTER": self.args.master
            or f"127.0.0.1:{self.store.port}",
            "PADDLE_ELASTIC_GENERATION": str(self.generation),
        })
        # elastic-supervisor contract (distributed/resilience/supervisor):
        # restart budget follows the launcher's, and a worker spawned
        # into a re-formed pod knows it is rejoining (so its supervisor
        # bumps the rendezvous generation instead of matching a stale one)
        env["PT_SUPERVISOR_MAX_RESTARTS"] = str(self.args.max_restart)
        if self.args.ckpt_dir:
            env["PT_CKPT_ROOT"] = self.args.ckpt_dir
        if self.args.snapshot_every > 0:
            env["PT_SNAPSHOT_EVERY"] = str(self.args.snapshot_every)
        if self.generation > 0:
            env["PT_SUPERVISOR_REJOIN"] = "1"
        # host-level fault domain contract: workers learn the standby
        # store endpoint (FailoverStore redial target) and their host_id
        # (membership + ring placement); an explicit PT_HOST_ID from the
        # environment (chaos tests) wins over the controller's host
        if self.args.standby:
            env.setdefault("PT_STORE_STANDBY", self.args.standby)
        env.setdefault("PT_HOST_ID", self.host)
        return env

    def spawn(self, pod: Pod):
        os.makedirs(self.args.log_dir, exist_ok=True)
        for i in range(pod.local_procs):
            log = open(os.path.join(
                self.args.log_dir,
                f"workerlog.{pod.rank * pod.local_procs + i}"), "ab")
            p = subprocess.Popen(
                [sys.executable, self.args.training_script]
                + self.args.training_script_args,
                env=self._worker_env(pod, i),
                stdout=log, stderr=subprocess.STDOUT)
            pod.procs.append(p)

    # -- watch loop --------------------------------------------------------
    # reference manager.py:32 — single source of truth in elastic.py
    from ..elastic import ELASTIC_EXIT_CODE

    def watch(self, pod: Pod):
        """Returns ("done", 0) | ("exit", code) | ("reform", generation).

        Elastic (reference fleet/elastic/manager.py:124-277): a worker
        exiting with ELASTIC_EXIT_CODE, a stale peer heartbeat, or a
        generation bump by another controller all trigger pod
        re-formation (fresh rendezvous, remapped ranks)."""
        restarts = 0
        while True:
            if self.elastic:
                self._heartbeat(pod)
                bump = self.store.add(f"{self.args.job_id}/gen_bump", 0)
                if bump > self.generation:
                    self._kill(pod)
                    return ("reform", bump)
                stale = self._stale_peer(pod)
                if stale is not None:
                    print(f"[launch] elastic: node {stale} heartbeat "
                          f"stale; re-forming pod", file=sys.stderr)
                    self._kill(pod)
                    return ("reform",
                            self.store.add(f"{self.args.job_id}/gen_bump",
                                           1))
                # watchdog escalation (distributed/watchdog.py) marks a
                # stalled group unhealthy in the store — a hung rank
                # still heartbeats, so this is the only signal that
                # catches a desync/deadlock (vs a dead process)
                unhealthy = self._unhealthy_group()
                if unhealthy is not None:
                    print(f"[launch] elastic: group {unhealthy} marked "
                          f"unhealthy by comm watchdog; re-forming pod",
                          file=sys.stderr)
                    self._clear_unhealthy(unhealthy)
                    self._kill(pod)
                    return ("reform",
                            self.store.add(f"{self.args.job_id}/gen_bump",
                                           1))
                # scale-out: a node joined this generation after we
                # settled — re-form so it gets a rank
                n_now = self.store.add(f"{self._ns()}/nodes", 0)
                if n_now > len(pod.world) \
                        and len(pod.world) < self.max_nodes:
                    print(f"[launch] elastic: {n_now} nodes registered "
                          f"(pod has {len(pod.world)}); re-forming",
                          file=sys.stderr)
                    self._kill(pod)
                    return ("reform",
                            self.store.add(f"{self.args.job_id}/gen_bump",
                                           1))
            statuses = [p.poll() for p in pod.procs]
            if all(s == 0 for s in statuses if s is not None) and \
                    all(s is not None for s in statuses):
                return ("done", 0)
            failed = [s for s in statuses if s not in (None, 0)]
            if failed:
                self._kill(pod)
                if self.elastic:
                    if self.ELASTIC_EXIT_CODE not in failed:
                        # real failures accumulate ACROSS re-formations
                        # (watch()-local counters would reset each time
                        # and the budget could never trip)
                        self._worker_failures += 1
                        if self._worker_failures > self.args.max_restart:
                            return ("exit", failed[0])
                    print(f"[launch] worker exit {failed[0]}; elastic "
                          f"re-formation", file=sys.stderr)
                    return ("reform",
                            self.store.add(f"{self.args.job_id}/gen_bump",
                                           1))
                if restarts >= self.args.max_restart:
                    print(f"[launch] worker failed (exit {failed[0]}); "
                          f"restart budget exhausted", file=sys.stderr)
                    return ("exit", failed[0])
                restarts += 1
                print(f"[launch] worker failed (exit {failed[0]}); "
                      f"restart {restarts}/{self.args.max_restart}",
                      file=sys.stderr)
                pod.procs = []
                self.spawn(pod)
            time.sleep(0.5)

    def _kill(self, pod: Pod):
        for p in pod.procs:
            if p.poll() is None:
                p.terminate()
        for p in pod.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        pod.procs = []

    def _heartbeat(self, pod: Pod):
        self._heartbeat_now(pod.rank)

    def _heartbeat_now(self, rank: int):
        if self.store is not None:
            self.store.set(f"{self._ns()}/hb/{rank}", str(time.time()))

    def _unhealthy_group(self):
        """Group id marked unhealthy by a worker's watchdog escalation
        (only the world group 0 is checked — sub-group desyncs stall
        the world group's next collective anyway), or None."""
        from ..watchdog import read_unhealthy

        return 0 if read_unhealthy(self.store, 0) is not None else None

    def _clear_unhealthy(self, gid: int):
        """Consume/clear an ``__unhealthy__`` mark. Also called before
        every (re-)spawn: a mark set by a dying worker AFTER the re-form
        decision must not immediately re-trigger escalation against the
        fresh pod."""
        from ..watchdog import clear_unhealthy

        try:
            clear_unhealthy(self.store, gid)
        except Exception as e:
            # the store owner may be mid-death; the next watch iteration
            # retries — losing the delete only delays one re-form
            print(f"[launch] could not clear unhealthy mark: {e!r}",
                  file=sys.stderr)

    def _stale_peer(self, pod: Pod):
        now = time.time()
        for r in range(len(pod.world)):
            if r == pod.rank:
                continue
            try:
                ts = float(self.store.get_nowait(
                    f"{self._ns()}/hb/{r}"))
                self._missing_since.pop((self.generation, r), None)
            except Exception:
                # never-written heartbeat: TTL clock starts at first
                # sighting (a node dead between register and first
                # heartbeat must not stall the pod forever)
                first = self._missing_since.setdefault(
                    (self.generation, r), now)
                if now - first > self.args.elastic_ttl:
                    return r
                continue
            if now - ts > self.args.elastic_ttl:
                return r
        return None

    def run(self) -> int:
        pod = None
        reforms = 0
        try:
            while True:
                pod = self.build_pod()
                if self.elastic:
                    # a stale mark from the previous incarnation must
                    # not trip the watchdog consumer on the fresh pod
                    self._clear_unhealthy(0)
                self.spawn(pod)
                result, arg = self.watch(pod)
                if result == "done":
                    return 0
                if result == "exit":
                    return arg
                # re-form at the (possibly newer) generation
                self.generation = max(
                    arg, self.store.add(f"{self.args.job_id}/gen_bump", 0))
                reforms += 1
                if reforms > max(self.args.max_restart, 3) * 3:
                    print("[launch] elastic re-formation budget "
                          "exhausted", file=sys.stderr)
                    return 1
        finally:
            if pod is not None:
                self._kill(pod)
            if self.store is not None:
                self.store.close()


def launch(argv=None) -> int:
    args = parse_args(argv)
    check_one_process_per_chip(args.nproc_per_node, os.environ)
    return Controller(args).run()


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()
