"""The sweep that finds a serving cell's knee, once, on the chip.

    python3 benchmark/sweep_serve.py --workload <cell> --rates 1,1.5,2,2.5

A new process for each rate (the engine's float32 build needs nearly the
whole chip, so nothing of an earlier run may be left on it; the parent
never touches JAX); in it the cell's own runner drives the cell's mix at
that rate for --seconds. A rate is sustained where the backlog does not
grow: the queue wait stays flat and the run ends soon after its window.
The cell then offers 0.8 of the highest sustained rate, written into its
traffic file as a number (PERF.md records the sweep).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=424242)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    if len(rates) > 1:
        import subprocess

        for rate in rates:
            subprocess.run([sys.executable, os.path.abspath(__file__),
                            "--workload", args.workload, "--rates",
                            str(rate), "--seconds", str(args.seconds),
                            "--seed", str(args.seed)], check=False)
        return 0
    from benchmark import harness
    from benchmark.runners import serve

    cell = harness.Cell.find(args.workload)
    harness.setup_compile_cache()
    devices = harness.require_tpu(cell.chips)
    for rate in rates:
        cell.traffic["rate_per_s"] = rate
        r = serve.run(cell, args.seed, args.seconds, False, devices,
                      time.perf_counter())
        keep = ("requests", "steps", "drain_s", "late_end_s",
                "step_ms_median")
        waits = r.stats["queue_wait_ms"]
        print(json.dumps({
            "rate_per_s": rate, "attempted": r.attempted, "failed": r.failed,
            **r.end_to_end, **{k: r.stats[k] for k in keep},
            "queue_wait_p50_ms": harness.percentile(waits, 50),
            "queue_wait_p95_ms": harness.percentile(waits, 95),
            "served_logit_gap": r.compared["served_logit_gap"].value}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
