"""One cell, once:  python3 benchmark/run.py --workload <name> --seed <n>
                                             --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json and its files by name, fails (exit code
not 0, no result line) without the TPU chips the cell asks for, warms up,
measures for --seconds, checks what the timed path produced against the
plain reference, prints each number compared beside its limit on standard
error, and prints the result as the last line of standard output.
--trace 0 reports the cell's end-to-end metrics, --trace 1 its per-layer
metrics (a profiler trace of the first seconds of the window, reduced by
`reduce_trace.py`).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()           # set-up is counted from here

import argparse                          # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import sys                               # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(cell, result, devices, trace: bool, peak) -> dict:
    """The contract's last line."""
    from benchmark import harness, reduce_trace

    units = {m["name"]: m["unit"] for m in cell.manifest["end_to_end"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": result.memory_peak_bytes}
    line = {"correct": result.correct, "attempted": result.attempted,
            "failed": result.failed}
    if trace:
        window = reduce_trace.window_of(result.trace)
        line["metrics"] = harness.per_layer_metrics(cell, result, peak)
        device["busy_s"] = reduce_trace.busy_seconds(result.trace, window)
        device["window_s"] = window[1] - window[0]
        line["device"] = device
        line["breakdown"] = reduce_trace.breakdown(result.trace, window)
    else:
        missing = [n for n in cell.end_to_end_names()
                   if n not in result.end_to_end]
        if missing:
            raise harness.BenchmarkError(
                f"the runner reported no {missing} for {cell.name}")
        line["metrics"] = {n: {"value": result.end_to_end[n],
                               "unit": units[n]}
                           for n in cell.end_to_end_names()}
        line["device"] = device
    # where set-up went (seconds since the start), for the next reader
    line["setup_phases_s"] = result.stats.get("setup_phases_s")
    if result.stats.get("not_compared"):
        line["not_compared"] = result.stats["not_compared"]
    line["compiles_in_window"] = len(result.compiles_in_window)
    line["compared"] = {
        k: {"value": c.value, "limit": c.limit,
            **({"where": c.where} if c.where else {})}
        for k, c in result.compared.items()}
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    from benchmark import harness

    try:
        cell = harness.Cell.find(args.workload)
        harness.check_named_files(cell.config)
        harness.setup_compile_cache()
        devices = harness.require_tpu(cell.chips)
        peak = harness.load_peak(devices[0].device_kind)
        runner = harness.load_module("runners", cell.config["entry"])
        result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                            devices, T_START)
        line = result_line(cell, result, devices, bool(args.trace), peak)
    except harness.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    for name, c in line["compared"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "NOT WITHIN ITS LIMIT"
        print(f"compared {name}: {c['value']:.6g} limit {c['limit']:.6g} "
              f"{verdict} {c.get('where', '')}".rstrip(), file=sys.stderr)
    if line["compiles_in_window"]:
        print(f"compared compiles_in_window: {line['compiles_in_window']} "
              f"limit 0 NOT WITHIN ITS LIMIT", file=sys.stderr)
    if line["failed"]:
        print(f"compared failed: {line['failed']} limit 0 "
              f"NOT WITHIN ITS LIMIT", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
