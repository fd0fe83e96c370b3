# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
# Every module runs in this one process: a process that has touched JAX owns
# the accelerator, so no module may start a child that needs JAX.
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from benchmarks import (fig7_selective, fig8_cache_modes, fig10_inmemory,
                            fig_app_zoo, fig_autotune, fig_batch_frontiers,
                            fig_cache_tiers, fig_delta_incremental,
                            fig_multidevice, fig_pipeline_overlap,
                            fig_serve_throughput, kernel_spmv,
                            roofline_report, table2_compression,
                            table3_io_model, table5_apps, table8_preprocessing)
    modules = [
        ("table2_compression", table2_compression),
        ("table3_io_model", table3_io_model),
        ("table5_apps (tables 5-7)", table5_apps),
        ("fig_app_zoo", fig_app_zoo),
        ("table8_preprocessing", table8_preprocessing),
        ("fig7_selective", fig7_selective),
        ("fig8_cache_modes", fig8_cache_modes),
        ("fig10_inmemory (figs 9-10)", fig10_inmemory),
        ("fig_batch_frontiers", fig_batch_frontiers),
        ("fig_cache_tiers", fig_cache_tiers),
        ("fig_pipeline_overlap", fig_pipeline_overlap),
        ("fig_multidevice", fig_multidevice),
        ("fig_serve_throughput", fig_serve_throughput),
        ("fig_delta_incremental", fig_delta_incremental),
        ("fig_autotune", fig_autotune),
        ("kernel_spmv", kernel_spmv),
        ("roofline_report", roofline_report),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        t0 = time.time()
        try:
            for line in mod.run():
                print(line, flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},0.0,EXCEPTION", flush=True)
            traceback.print_exc(file=sys.stderr)
        print(f"# {name} took {time.time()-t0:.1f}s", file=sys.stderr, flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
