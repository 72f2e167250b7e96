# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows. Usage: PYTHONPATH=src python -m benchmarks.run [--only fig4,...]
#                  [--json [PATH]]   (default PATH: BENCH_endtoend.json)
import argparse
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig4,table2")
    ap.add_argument("--json", nargs="?", const="BENCH_endtoend.json",
                    default=None, metavar="PATH",
                    help="also write structured results as JSON "
                         "(default: BENCH_endtoend.json) so future PRs "
                         "have a perf trajectory to compare against")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (common, fig4_throughput, fig6_overheads,
                            fig7_10_parallel, fig11_pareto, fig12_cpu_accel,
                            fig13_endtoend, fig14_capacity, fig15_trace,
                            roofline_table, table2_3_cost)
    suites = {
        "fig4": fig4_throughput.run,
        "fig6": fig6_overheads.run,
        "fig7_10": fig7_10_parallel.run,
        "fig11": fig11_pareto.run,
        "fig12": fig12_cpu_accel.run,
        "fig13": fig13_endtoend.run,
        "fig14": fig14_capacity.run,
        "fig15": fig15_trace.run,
        "table2": table2_3_cost.run,
        "roofline": roofline_table.run,
    }
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        if only and name not in only:
            continue
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"suites": sorted((only or set(suites)) & set(suites)),
                       "failed": failed,
                       "results": common.RESULTS}, f, indent=2)
        print(f"wrote {len(common.RESULTS)} rows to {args.json}",
              file=sys.stderr)
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
