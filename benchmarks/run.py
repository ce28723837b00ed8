"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).

    PYTHONPATH=src python -m benchmarks.run [--only table1]
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run a single benchmark "
                         "(table1|table2|table3|fig5|kernels|serve|pareto|"
                         "train|roofline)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, no BENCH_*.json overwrite — the CI "
                         "leg that keeps benchmark scripts from rotting "
                         "(kernels, serve, pareto and train support it; "
                         "others ignore it)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (fig5_pid, kernel_bench, pareto_bench, serve_bench,
                            table1_train_time, table2_jsc_hlf, table3_plf_tgc,
                            train_bench)

    benches = {
        "table1": table1_train_time.run,
        "table2": table2_jsc_hlf.run,
        "table3": table3_plf_tgc.run,
        "fig5": fig5_pid.run,
        # smoke-aware: tiny shapes + no JSON write under --smoke
        "kernels": lambda: kernel_bench.run(smoke=args.smoke),
        "serve": lambda: serve_bench.run(smoke=args.smoke),
        "pareto": lambda: pareto_bench.run(smoke=args.smoke),
        "train": lambda: train_bench.run(smoke=args.smoke),
    }
    print("name,us_per_call,derived")
    todo = [args.only] if args.only else list(benches) + ["roofline"]
    for name in todo:
        if name == "roofline":
            # roofline terms come from the dry-run artifact, if present
            import os
            src = next((p for p in ("results/dryrun_final.jsonl",
                                    "results/dryrun_all.jsonl")
                        if os.path.exists(p)), None)
            if src:
                from benchmarks import roofline
                rows = [roofline.analyze_record(r)
                        for r in roofline.load(src)]
                for a in rows:
                    print(f"roofline/{a['arch']}/{a['shape']}/{a['mesh']},0.0,"
                          f"dominant={a['dominant']};rMFU={a['roofline_mfu']:.3f};"
                          f"useful={a['useful_ratio']:.3f}")
            else:
                print("roofline/skipped,0.0,no_dryrun_artifact", flush=True)
            continue
        t0 = time.time()
        benches[name]()
        print(f"{name}/total_wall_s,{(time.time()-t0)*1e6:.0f},ok", flush=True)


if __name__ == "__main__":
    main()
