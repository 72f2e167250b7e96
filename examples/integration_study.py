"""Reproduce the paper's integration study end-to-end on this machine:
stage-overhead decomposition (Fig 6), parallel-config sweep (Figs 7-10),
Pareto (Fig 11), CPU-vs-accelerator crossover (Fig 12), and the cost
tables (Tables 2-3) — printed as a single report.

Run:  PYTHONPATH=src python examples/integration_study.py
"""
from repro.compile_cache import enable_compile_cache
from benchmarks import (fig4_throughput, fig6_overheads, fig7_10_parallel,
                        fig11_pareto, fig12_cpu_accel, table2_3_cost)


def main():
    print("name,us_per_call,derived")
    print("# --- Fig 4: stand-alone throughput vs batch (v1 vs v2) ---")
    fig4_throughput.run()
    print("# --- Fig 6: stage overhead decomposition ---")
    fig6_overheads.run()
    print("# --- Figs 7-10: parallel configuration series ---")
    fig7_10_parallel.run()
    print("# --- Fig 11: Pareto front ---")
    fig11_pareto.run()
    print("# --- Fig 12: CPU vs accelerator crossover ---")
    fig12_cpu_accel.run()
    print("# --- Tables 2-3: deployment cost ---")
    table2_3_cost.run()


if __name__ == "__main__":
    enable_compile_cache()
    main()
