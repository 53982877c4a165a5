"""Merge a host chrome-trace with a metrics snapshot into one report.

Inputs:
  --trace    chrome-trace JSON written by paddle.profiler.Profiler.export
             (traceEvents with ph="X" duration spans)
  --metrics  JSON snapshot written by paddle.profiler.metrics
             (snapshot_to_file / enable_periodic_flush / PT_METRICS_FLUSH_PATH)

Either input may be omitted; the report covers what it is given. Output
is a human-readable text report: a span summary table (calls, total,
avg, max per span name), the counters/gauges, and histogram summaries
with bucket-estimated p50/p95 — the triage view that answers "where did
the time go" without opening perfetto.

Usage:
  python tools/trace_report.py --trace /tmp/prof/worker.json \
      --metrics /tmp/metrics.json [-o report.txt]
"""
from __future__ import annotations

import argparse
import json
from collections import defaultdict

# The framework's metric-name inventory — the single known set shared by
# this report, the README "Observability" section, and the PT403 lint
# rule (paddle_tpu/analysis/registry_rules.py), which statically checks
# every literal metric name emitted in paddle_tpu/ against it. '*'
# entries cover dynamically-built families (f-string / concatenated
# names). Names outside this set render with an "(unknown)" marker below
# and fail ptlint at the emit site.
KNOWN_METRICS = (
    # op-dispatch funnel (core/dispatch.py, ops/registry.py)
    "dispatch/calls", "dispatch/cache_hit", "dispatch/cache_miss",
    "dispatch/uncacheable", "dispatch/cache_disabled_calls",
    "dispatch/cache_evictions", "dispatch/cache_fallbacks",
    # Pallas kernels (ops/pallas/__init__.py): trace-time decisions to
    # run a kernel's jnp reference although kernels are on (the shape
    # does not tile) — chip_smoke.py asserts zero on its path
    "pallas/reference_dispatch", "pallas/reference_dispatch/*",
    # jit compile bridge (jit/api.py, jit/partial_capture.py)
    "jit/compile_count", "jit/compile_ms", "jit/retrace_count",
    "jit/retrace_cause/*", "jit/graph_break_count",
    "jit/partial_regions", "jit/partial_regions_installed",
    "jit/region_break_count",
    # collectives (distributed/collective.py)
    "comm/collective_count", "comm/collective_bytes", "comm/latency_ms",
    "comm/*_count", "comm/*_bytes",
    # collective-compute overlap (meta_parallel: stage-3 param prefetch,
    # latency-hidden pipeline sends / 1F1B hand-off windows)
    "comm/overlap_ms",
    # fusion compiler (static/passes.py auto_fuse + static/stablehlo.py)
    "compiler/fused_regions", "compiler/est_bytes_saved",
    "compiler/auto_fuse_ms", "compiler/stablehlo_emissions",
    # transport reliability + watchdog escalation
    # (distributed/transport.py, distributed/watchdog.py)
    "comm/retries", "comm/redials", "comm/corrupt_frames",
    "comm/dup_frames", "comm/watchdog_escalations",
    "comm/escalation_errors", "comm/escalation_store_errors",
    "comm/close_errors", "comm/peer_close_errors",
    "comm/recv_loop_close_errors",
    # elastic manager (distributed/elastic.py) + supervisor re-form
    "elastic/heartbeat_errors", "elastic/last_beat_ts",
    "elastic/membership_changes", "elastic/unhealthy_cleared",
    # host-level fault domains: quorum gate + generation fencing
    # (distributed/resilience/supervisor.py)
    "elastic/quorum_checks", "elastic/quorum_ok", "elastic/quorum_lost",
    "elastic/fenced_writes", "elastic/stale_snapshots_dropped",
    # replicated rendezvous store: hot standby + client failover
    # (distributed/store.py)
    "store/failovers", "store/redials", "store/tailer_drops",
    "store/replicated_records", "store/replication_naks",
    "store/standby_takeovers",
    # chaos injector (distributed/resilience/faults.py)
    "faults/injected", "faults/*",
    # self-healing training loop (distributed/resilience/supervisor.py
    # + guards.py): restarts/re-forms, recovery tiers, snapshot ring,
    # numerical-anomaly policy, SDC agreement probe
    "train/restarts", "train/reform_ms", "train/recovery_source/*",
    "train/steps", "train/snapshots", "train/snapshot_bytes",
    "train/replication_errors", "train/anomalies",
    "train/skipped_batches", "train/rollbacks", "train/sdc_flags",
    "train/step_ms",
    # checkpoint retention (distributed/resilience/recovery.py)
    "ckpt/pruned", "ckpt/swept_incomplete",
    # serving engine (inference/serving.py)
    "serving/ttft_ms", "serving/tpot_ms", "serving/steps",
    "serving/tokens_generated", "serving/requests",
    "serving/preemptions", "serving/batch_occupancy",
    "serving/kv_cache_utilization", "serving/deadline_evictions",
    "serving/load_shed",
    # what each engine step held, bumped once a step: scheduled rows,
    # real tokens, padding up to the static token length, prompt tokens
    "serving/step_rows", "serving/step_tokens",
    "serving/step_pad_tokens", "serving/step_prefill_tokens",
    # of serving/steps, those whose program holds the paged-attention
    # Pallas kernel (0 off the chip and on exported artifacts)
    "serving/paged_kernel_steps", "serving/kv_inplace_steps",
    # of serving/steps, those dispatched with the step before them still
    # unsettled; and the settles something other than the next step
    # asked for (ServingEngine.settle)
    "serving/lookahead_steps", "serving/settle_forced",
    # state-space layers over a row slot (inference/layer_states.py):
    # rows through the one-token state update, rows through the chunked
    # scan, rows that started from a zeroed slot
    "serving/ssm_rows_decode", "serving/ssm_rows_chunk",
    "serving/ssm_state_resets",
    # an expert layer that holds a share (models/nemotron_h.py): the
    # (token, expert) pairs this chip computed, the tokens routed (both
    # summed over the expert layers), and the fullest held expert's pairs
    "serving/moe_pairs_held", "serving/moe_tokens",
    "serving/moe_max_expert_tokens",
    # fleet serving tier: shared-prefix KV reuse (inference/
    # prefix_cache.py), multi-replica routing (inference/router.py),
    # disaggregated prefill/decode hand-offs (inference/disagg.py)
    "serving/prefix_hit_rate", "serving/prefix_pages_reused",
    "serving/reroutes", "serving/requeues", "serving/migrations",
    # serving resilience tier (inference/fleet_supervisor.py + router
    # half-open circuit breaker + prefix-cache persistence)
    "serving/replica_failures", "serving/replica_restored",
    "serving/replica_restarts", "serving/drains",
    "serving/drain_requeues",
    # cross-host serving failover: off-host drain targets + real
    # TensorTransport KV hand-offs (inference/fleet_supervisor.py)
    "serving/cross_host_drains", "serving/cross_host_migrations",
    # bounded deadline-requeue retries (inference/router.py)
    "serving/requeue_exhausted",
    # overload-safe traffic tier: SLO-class admission, tenant fairness,
    # retry budget, brownout ladder (inference/gateway.py)
    "gateway/*",
    "serving/prefix_hits_restored", "serving/cache_restore_ms",
    "serving/cache_snapshots", "serving/cache_snapshots_swept",
    "serving/cache_snapshots_pruned",
    # speculative decoding (inference/speculative.py + serving.py
    # _spec_step): drafted/accepted token funnel + per-step yield
    "serving/spec_steps", "serving/spec_drafted_tokens",
    "serving/spec_accepted_tokens", "serving/spec_accept_rate",
    "serving/spec_tokens_per_step",
    # whole-iteration decode executables (decode windows + speculative
    # verify shapes) the engine compiled — the fused-decode region count
    "compiler/fused_decode_regions",
    # int8/int4 double-buffered weight streaming
    # (inference/weight_stream.py)
    "weights/stream_prefetch_ms",
    # live weight publishing (inference/weight_publish.py): per-engine
    # swap state + fleet rollout funnel (publishes / refusals / canary
    # verdicts / shipped bytes + wall time / restart catch-ups /
    # replicas that missed a rollout) and the speculative-drafter
    # hand-off across a swap (republish vs n-gram fallback, post-swap
    # accept-rate collapse alarms)
    "serving/weight_version", "serving/weight_swaps",
    "serving/weight_rollbacks", "serving/weight_publishes",
    "serving/publish_rejected", "serving/canary_failures",
    "serving/publish_bytes", "serving/publish_ms",
    "serving/publish_catchups", "serving/publish_missed",
    "serving/spec_drafter_republished", "serving/spec_drafter_fallbacks",
    "serving/spec_accept_alarms",
    # Executor-tier auto_fuse fallback (static/__init__.py)
    "compiler/executor_fuse_reverts",
    # IR-level program analyzer (paddle_tpu/analysis/program/)
    "analysis/programs_analyzed", "analysis/ops_analyzed",
    "analysis/findings", "analysis/peak_bytes",
    "analysis/verify_failures",
    # concurrency analyzer (ptrace: PT7xx races + PT8xx protocols)
    "analysis/conc_runs", "analysis/conc_findings",
    # sharding propagation (ptshard: PT9xx) + the static auto-tuner it
    # powers (distributed/auto_tuner/static_tuner.py)
    "analysis/shard_runs", "analysis/shard_findings",
    "analysis/tuner_configs_ranked", "analysis/tuner_rank_ms",
    # distributed tracing + crash flight recorder (profiler/tracing.py)
    "trace/*",
    # fleet metrics aggregation plane (profiler/aggregate.py):
    # snapshot shipping, replica census, clock-offset estimation
    "fleet/*", "fleet/stale_evictions",
    # SLO engine (profiler/timeline.py, slo.py, headroom.py): sampling
    # ring + spill, outcome accounting, burn alerts, scale advisories
    "timeline/*", "slo/*",
    # reason-coded gateway terminal outcomes (inference/gateway.py)
    "gateway/outcome/*",
    # elastic fleet resizing (inference/autoscaler.py): resize actions,
    # spawn retries, catch-up/drain latencies, freeze accounting
    "autoscale/actions", "autoscale/spawn_failures",
    "autoscale/catchup_ms", "autoscale/drain_ms",
    "autoscale/frozen_evals", "autoscale/fleet_size",
    # process-isolated replicas (inference/remote_replica.py): child
    # spawns, heartbeat-declared process deaths, orphan-sweep reaps
    "serving/replica_spawns", "serving/replica_process_deaths",
    "serving/orphans_reaped",
)


def _known(name: str) -> bool:
    import fnmatch

    return any(name == p or ("*" in p and fnmatch.fnmatchcase(name, p))
               for p in KNOWN_METRICS)


def _tag(name: str) -> str:
    return name if _known(name) else name + " (unknown)"


def summarize_trace(trace: dict) -> str:
    events = trace.get("traceEvents", [])
    agg = defaultdict(lambda: [0, 0.0, 0.0])        # calls, total_us, max_us
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name", "?")
        dur = float(ev.get("dur", 0.0))
        a = agg[name]
        a[0] += 1
        a[1] += dur
        if dur > a[2]:
            a[2] = dur
    if not agg:
        return "  (no duration spans in trace)"
    lines = [f"  {'Span':<44} {'Calls':>8} {'Total(ms)':>11} "
             f"{'Avg(ms)':>9} {'Max(ms)':>9}"]
    for name, (calls, total, mx) in sorted(agg.items(),
                                           key=lambda kv: -kv[1][1]):
        lines.append(f"  {name[:44]:<44} {calls:>8} {total / 1e3:>11.3f} "
                     f"{total / calls / 1e3:>9.3f} {mx / 1e3:>9.3f}")
    if trace.get("xplane_dir"):
        lines.append(f"  device XPlane dir: {trace['xplane_dir']}")
    return "\n".join(lines)


def _hist_quantile(h: dict, q: float):
    """Digest quantile when the snapshot carries one (exact-ish, the
    t-digest value computed registry-side), else bucket-estimated
    (upper bound of the covering bucket)."""
    key = {0.5: "p50", 0.95: "p95", 0.99: "p99"}.get(q)
    if key is not None and h.get(key) is not None:
        return h[key]
    total = h.get("count", 0)
    if not total:
        return None
    target = q * total
    acc = 0
    for bound, c in sorted(h.get("buckets", {}).items(),
                           key=lambda kv: float(kv[0])):
        acc += c
        if acc >= target:
            return float(bound)
    return h.get("max")


def summarize_metrics(snap: dict) -> str:
    lines = []
    counters = snap.get("counters", {})
    gauges = snap.get("gauges", {})
    hists = snap.get("histograms", {})
    if counters:
        lines.append("  Counters:")
        for name in sorted(counters):
            lines.append(f"    {_tag(name):<44} {counters[name]}")
    if gauges:
        lines.append("  Gauges:")
        for name in sorted(gauges):
            v = gauges[name]
            v = f"{v:.4f}" if isinstance(v, float) else v
            lines.append(f"    {_tag(name):<44} {v}")
    if hists:
        lines.append("  Histograms:")
        lines.append(f"    {'Name':<34} {'Count':>7} {'Avg':>10} "
                     f"{'Min':>10} {'~p50':>10} {'~p95':>10} {'Max':>10}")
        for name in sorted(hists):
            h = hists[name]

            def fmt(v):
                return f"{v:.3f}" if isinstance(v, (int, float)) else "-"

            lines.append(
                f"    {name[:34]:<34} {h.get('count', 0):>7} "
                f"{fmt(h.get('avg')):>10} {fmt(h.get('min')):>10} "
                f"{fmt(_hist_quantile(h, 0.5)):>10} "
                f"{fmt(_hist_quantile(h, 0.95)):>10} "
                f"{fmt(h.get('max')):>10}")
    return "\n".join(lines) if lines else "  (empty snapshot)"


def merge_traces(traces, offsets=None) -> dict:
    """Merge per-host chrome traces onto one timeline.

    `offsets` (seconds, one per trace; see
    paddle_tpu.profiler.aggregate.estimate_clock_offset) is ADDED to
    each trace's timestamps to land them on the reference host's clock.
    Span ids/trace ids pass through untouched — a request migrated
    between hosts keeps one trace id across the merged file."""
    out = {"traceEvents": [], "displayTimeUnit": "ms"}
    for i, tr in enumerate(traces):
        off_us = (offsets[i] if offsets and i < len(offsets) else 0.0) * 1e6
        for ev in tr.get("traceEvents", []):
            ev = dict(ev)
            if "ts" in ev:
                ev["ts"] = float(ev["ts"]) + off_us
            ev.setdefault("args", {})
            ev["args"].setdefault("source_trace", i)
            out["traceEvents"].append(ev)
    out["traceEvents"].sort(key=lambda e: e.get("ts", 0.0))
    return out


def trace_tree_check(trace: dict) -> dict:
    """Connectivity census over span ids: how many distinct trace ids,
    and which ones span more than one pid (a request that moved between
    engines/hosts but kept one trace id — the migration invariant)."""
    by_trace = defaultdict(set)
    for ev in trace.get("traceEvents", []):
        args = ev.get("args", {})
        tid = args.get("trace_id")
        if tid:
            by_trace[tid].add((ev.get("pid"), args.get("engine")))
    cross = sorted(t for t, owners in by_trace.items() if len(owners) > 1)
    return {"n_traces": len(by_trace), "cross_process": cross}


def straggler_section(snaps, metric: str = "train/step_ms",
                      factor: float = 1.5) -> str:
    """Per-rank p95 comparison across metrics snapshots: flag ranks
    whose `metric` p95 exceeds `factor` x the fleet median p95. Uses
    the digest percentiles embedded in each histogram snapshot."""
    rows = []
    for i, snap in enumerate(snaps):
        h = snap.get("histograms", {}).get(metric)
        if not h:
            continue
        who = snap.get("replica") or snap.get("namespace") \
            or f"snap{i}(pid{snap.get('pid')})"
        host = snap.get("host_id")
        if host:
            who = f"{host}/{who}"
        rows.append((who, h.get("count", 0), _hist_quantile(h, 0.5),
                     _hist_quantile(h, 0.95), h.get("max")))
    if not rows:
        return f"  (no {metric} histograms across snapshots)"
    p95s = sorted(r[3] for r in rows if r[3] is not None)
    median = p95s[len(p95s) // 2] if p95s else None
    lines = [f"  {'Rank':<30} {'Count':>7} {'p50':>10} {'p95':>10} "
             f"{'Max':>10}  flag"]
    for who, count, p50, p95, mx in sorted(rows):
        flag = "STRAGGLER" if (median and p95 is not None
                               and p95 > factor * median) else ""
        def fmt(v):
            return f"{v:.3f}" if isinstance(v, (int, float)) else "-"
        lines.append(f"  {who[:30]:<30} {count:>7} {fmt(p50):>10} "
                     f"{fmt(p95):>10} {fmt(mx):>10}  {flag}")
    if median is not None:
        lines.append(f"  (median p95 {median:.3f}, straggler threshold "
                     f"{factor:g}x = {factor * median:.3f})")
    return "\n".join(lines)


def build_report(trace: dict = None, metrics: dict = None) -> str:
    parts = ["paddle_tpu trace report", "=" * 70]
    if metrics is not None:
        ts = metrics.get("ts")
        head = "Metrics snapshot"
        if ts:
            import datetime

            head += " @ " + datetime.datetime.fromtimestamp(ts).isoformat()
        parts += [head, "-" * 70, summarize_metrics(metrics), ""]
    if trace is not None:
        parts += ["Host span summary", "-" * 70, summarize_trace(trace), ""]
    if trace is None and metrics is None:
        parts.append("(nothing to report: pass --trace and/or --metrics)")
    return "\n".join(parts)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace", action="append", default=[],
                    help="chrome-trace JSON (Profiler.export or "
                         "tracing.export_chrome); repeat for a "
                         "multi-host merge")
    ap.add_argument("--clock-offset", action="append", default=[],
                    type=float, metavar="SECONDS",
                    help="per --trace clock offset (aggregate."
                         "estimate_clock_offset), positional match; "
                         "missing entries default to 0")
    ap.add_argument("--metrics", action="append", default=[],
                    help="metrics snapshot JSON; repeat for a per-rank "
                         "straggler report")
    ap.add_argument("--straggler-metric", default="train/step_ms",
                    help="histogram compared across ranks "
                         "(default: train/step_ms)")
    ap.add_argument("--merged-trace", help="also write the merged "
                                           "chrome trace JSON here")
    ap.add_argument("-o", "--output", help="write report here "
                                           "(default: stdout)")
    args = ap.parse_args(argv)
    traces = []
    for path in args.trace:
        with open(path) as f:
            traces.append(json.load(f))
    snaps = []
    for path in args.metrics:
        with open(path) as f:
            snaps.append(json.load(f))
    trace = None
    if traces:
        trace = traces[0] if len(traces) == 1 \
            else merge_traces(traces, args.clock_offset)
    report = build_report(trace, snaps[0] if snaps else None)
    if len(snaps) > 1:
        report += "\n".join([
            "", f"Per-rank stragglers ({args.straggler_metric})",
            "-" * 70, straggler_section(snaps, args.straggler_metric), ""])
    if trace is not None and len(traces) > 1:
        tree = trace_tree_check(trace)
        report += "\n".join([
            "", "Merged-trace connectivity", "-" * 70,
            f"  {len(traces)} traces merged, {tree['n_traces']} distinct "
            f"trace ids, {len(tree['cross_process'])} spanning multiple "
            f"processes", ""])
    if args.merged_trace and trace is not None:
        with open(args.merged_trace, "w") as f:
            json.dump(trace, f)
    if args.output:
        with open(args.output, "w") as f:
            f.write(report + "\n")
    else:
        print(report)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
