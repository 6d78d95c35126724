"""Benchmark orchestrator: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Default sizes finish on one
CPU core; BENCH_SCALE=10 approaches the paper's regimes. Exits non-zero
when any module raised.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig4 table3 # subset
"""
from __future__ import annotations

import sys
import time
import traceback

from benchmarks import (fig4_mvm_error, fig6_mvm_speed, fig_build,
                        fig_elastic, fig_recovery, fig_rollout,
                        fig_scaling, fig_serve, fig_soak, fig_train_step,
                        roofline_report, table2_uci, table3_sparsity,
                        table4_cg)
from repro.utils.compile_cache import enable_compile_cache

MODULES = {
    "fig4": fig4_mvm_error,
    "table3": table3_sparsity,
    "fig6": fig6_mvm_speed,
    "fig_build": fig_build,
    "fig_train": fig_train_step,
    "fig_scaling": fig_scaling,
    "fig_serve": fig_serve,
    "fig_rollout": fig_rollout,
    "fig_soak": fig_soak,
    "fig_recovery": fig_recovery,
    "fig_elastic": fig_elastic,
    "table4": table4_cg,
    "table2": table2_uci,
    "roofline": roofline_report,
}


def main() -> int:
    wanted = sys.argv[1:] or list(MODULES)
    print("name,us_per_call,derived")
    failed = []
    for key in wanted:
        mod = MODULES[key]
        t0 = time.time()
        try:
            mod.main()
            print(f"{key}/TOTAL,{(time.time() - t0) * 1e6:.0f},ok")
        except Exception as e:  # keep the suite going; fail at the end
            traceback.print_exc()
            print(f"{key}/TOTAL,,ERROR {e}")
            failed.append(key)
    # machine-readable artifacts written by the modules (BENCH_*.json)
    from benchmarks.common import ARTIFACT_DIR
    arts = sorted(p.name for p in ARTIFACT_DIR.glob("BENCH_*.json"))
    if arts:
        print(f"# artifacts in {ARTIFACT_DIR}: {', '.join(arts)}")
    if failed:
        print(f"# FAILED: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
