"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = compact JSON of the
table-specific numbers, including the paper's reference values).
``--json-dir`` additionally writes one ``BENCH_<tag>.json`` per module —
the CI bench-smoke job uploads these as artifacts so the perf trajectory
is captured per PR.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only table3,fig2]
                                            [--json-dir bench-out]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

MODULES = [
    ("table1", "benchmarks.virt_overhead"),
    ("table2", "benchmarks.pd_bottlenecks"),
    ("table3", "benchmarks.pd_disagg_vs_dynamic"),
    ("table4", "benchmarks.colocation_ttft"),
    ("fig2", "benchmarks.decode_bandwidth"),
    ("fig56", "benchmarks.timeslice_sweep"),
    ("role_switch", "benchmarks.role_switch"),
    ("slo_attainment", "benchmarks.slo_attainment"),
    ("prefix_reuse", "benchmarks.prefix_reuse"),
    ("kv_streaming", "benchmarks.kv_streaming"),
    ("microbatch_prefill", "benchmarks.microbatch_prefill"),
    ("roofline", "benchmarks.roofline"),
    ("kernels", "benchmarks.kernels_microbench"),
    ("sim_throughput", "benchmarks.sim_throughput"),
    ("predictive_sched", "benchmarks.predictive_sched"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--json-dir", default="",
                    help="write BENCH_<tag>.json per module into this dir")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    if args.json_dir:
        os.makedirs(args.json_dir, exist_ok=True)

    print("name,us_per_call,derived")
    failures = []
    for tag, modname in MODULES:
        if only and tag not in only:
            continue
        t0 = time.time()
        try:
            import importlib
            mod = importlib.import_module(modname)
            rows = mod.run(quick=args.quick)
        except Exception as e:  # keep the harness running
            failures.append((tag, repr(e)))
            print(f"{tag}.ERROR,0,{json.dumps(repr(e)[:120])}")
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.2f},{json.dumps(json.dumps(derived))}")
        elapsed = time.time() - t0
        print(f"# {tag} done in {elapsed:.1f}s", file=sys.stderr)
        if args.json_dir:
            from benchmarks._cli import rows_payload
            path = os.path.join(args.json_dir, f"BENCH_{tag}.json")
            with open(path, "w") as f:
                json.dump({"tag": tag, "module": modname,
                           "quick": args.quick,
                           "elapsed_s": round(elapsed, 2),
                           "rows": rows_payload(rows)}, f, indent=2)
    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
