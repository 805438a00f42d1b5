"""Benchmark harness — one module per paper table/figure plus the roofline
report.  Prints ``name,us_per_call,derived`` CSV lines per benchmark.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig1,table1,...]
"""
import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller contexts / fewer prompts")
    ap.add_argument("--only", default="",
                    help="comma list: fig1,table1,table2,table4,fig5,"
                         "fig6,fig4,roofline,kernels")
    args, _ = ap.parse_known_args()
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import (bench_fig1_bottleneck, bench_table1_speedup,
                            bench_table2_quality, bench_table4_reduction,
                            bench_fig5_qa, bench_fig6_refresh,
                            bench_fig4_offload, bench_roofline,
                            bench_kernels)
    suites = [
        ("roofline", lambda q: bench_roofline.main()),
        ("kernels", bench_kernels.main),
        ("fig1", bench_fig1_bottleneck.main),
        ("fig4", bench_fig4_offload.main),
        ("table1", bench_table1_speedup.main),
        ("table2", bench_table2_quality.main),
        ("table4", bench_table4_reduction.main),
        ("fig5", bench_fig5_qa.main),
        ("fig6", bench_fig6_refresh.main),
    ]
    failures = []
    for name, fn in suites:
        if only and name not in only:
            continue
        print(f"\n########## {name} ##########", flush=True)
        t0 = time.time()
        try:
            fn(args.quick)
        except Exception:  # noqa: BLE001 — keep the harness going
            failures.append(name)
            traceback.print_exc()
        print(f"[{name}] {time.time() - t0:.0f}s", flush=True)
    if failures:
        print(f"\nFAILED suites: {failures}")
        sys.exit(1)
    print("\nall benchmark suites completed")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
