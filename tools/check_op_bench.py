"""Regression gate over two op_bench.py runs (reference analog:
tools/check_op_benchmark_result.py). Fails (exit 1) if any op slowed by
more than --threshold (default 1.5x).

Usage: python tools/check_op_bench.py baseline.json current.json [--threshold=1.15]
"""
import json
import sys


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    thr = 1.5
    for a in sys.argv[1:]:
        if a.startswith("--threshold"):
            thr = float(a.split("=", 1)[1]) if "=" in a else thr
    base = json.load(open(args[0]))["ops"]
    cur = json.load(open(args[1]))["ops"]
    failures = []
    for name, t0 in base.items():
        t1 = cur.get(name)
        if t1 is None or t0 <= 0:
            continue
        ratio = t1 / t0
        mark = "SLOWER" if ratio > thr else "ok"
        print(f"{name:24s} {t0:.6f}s -> {t1:.6f}s  x{ratio:.3f}  {mark}")
        if ratio > thr:
            failures.append((name, ratio))
    if failures:
        print(f"FAIL: {len(failures)} op(s) regressed beyond x{thr}")
        sys.exit(1)
    print("PASS")


if __name__ == "__main__":
    main()
