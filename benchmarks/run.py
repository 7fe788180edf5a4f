"""Benchmark harness entrypoint: one benchmark per paper table/figure plus
kernels and the roofline reader. Emits CSV per benchmark (also written to
benchmarks/artifacts/) and a final ``name,us_per_call,derived`` summary.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig3,...]
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import emit

BENCHES = [
    ("fig3_convergence", "benchmarks.bench_convergence"),
    ("table4_network", "benchmarks.bench_network"),
    ("paper_scale", "benchmarks.bench_scale"),
    ("fig4_sample_params", "benchmarks.bench_sample_params"),
    ("fig5_membership", "benchmarks.bench_membership"),
    ("fig6_crash", "benchmarks.bench_crash"),
    ("kernels", "benchmarks.bench_kernels"),
    ("serve", "benchmarks.bench_serve"),
    ("sf_ablation", "benchmarks.bench_ablation_sf"),
    ("roofline", "benchmarks.roofline"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale populations (slow)")
    ap.add_argument("--only", default="",
                    help="comma-separated benchmark name filter")
    args = ap.parse_args()
    only = {s.strip() for s in args.only.split(",") if s.strip()}

    summary = []
    failed = []
    for name, module in BENCHES:
        if only and not any(o in name for o in only):
            continue
        print(f"\n=== {name} ===")
        t0 = time.time()
        mod = __import__(module, fromlist=["run"])
        try:
            rows = mod.run(quick=not args.full)
            status = f"rows={len(rows) if rows else 0}"
        except Exception as e:  # pragma: no cover
            status = f"ERROR {e!r}"
            failed.append(name)
            print(f"[bench] {name} failed: {e!r}", file=sys.stderr)
        dt = time.time() - t0
        summary.append({"name": name,
                        "us_per_call": round(dt * 1e6, 0),
                        "derived": status})

    print("\n=== summary (name,us_per_call,derived) ===")
    emit(summary, "summary.csv")
    if failed:
        sys.exit(f"[bench] failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
