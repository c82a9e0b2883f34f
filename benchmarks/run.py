"""Benchmark orchestrator: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Latency values come from the
paper-calibrated flash simulator (DESIGN.md §6) except fig13/fig8 selection
overhead, which is real host wall-clock of the jit-compiled selector.

Usage:
  PYTHONPATH=src python -m benchmarks.run           # everything
  PYTHONPATH=src python -m benchmarks.run fig6 fig9 # subset
"""
from __future__ import annotations

import sys
import time

from repro.compile_cache import enable_compile_cache

from .common import Rows


def main() -> None:
    enable_compile_cache()
    from . import (
        appg_reorder,
        appk_token_density,
        appn_llm,
        disc5_caching,
        fig3_throughput,
        fig4_sparsity_latency,
        fig5_latency_model,
        fig6_tradeoff,
        fig8_breakdown,
        fig9_ablation,
        fig10_contiguity,
        fig13_overhead,
        roofline,
        serve_throughput,
        table1_cv,
        table3_bundling,
    )

    modules = {
        "fig3": fig3_throughput,
        "fig4": fig4_sparsity_latency,
        "fig5": fig5_latency_model,
        "fig6": fig6_tradeoff,
        "fig8": fig8_breakdown,
        "fig9": fig9_ablation,
        "fig10": fig10_contiguity,
        "fig13": fig13_overhead,
        "table1": table1_cv,
        "table3": table3_bundling,
        "appg": appg_reorder,
        "appk": appk_token_density,
        "appn": appn_llm,
        "disc5": disc5_caching,
        "roofline": roofline,
        "serve": serve_throughput,
    }
    selected = sys.argv[1:] or list(modules)
    rows = Rows()
    print("name,us_per_call,derived")
    for name in selected:
        mod = modules[name]
        t0 = time.time()
        mod.run(rows)
        rows.add(f"_meta/{name}/bench_wall_s", (time.time() - t0) * 1e6, "")
    rows.emit()


if __name__ == "__main__":
    main()
