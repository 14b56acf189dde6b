"""Benchmark entry point: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig3_5,kernels] [--fast]

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks.common).
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated module keys")
    ap.add_argument("--fast", action="store_true", help="reduced grids")
    args = ap.parse_args()

    if args.fast:
        os.environ["REPRO_BENCH_FAST"] = "1"

    # persistent compilation cache: the FL round programs are large
    # (unrolled S x U bodies) and identical across benchmark reruns
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    # imports AFTER env so benchmarks.common picks the flags up
    from benchmarks import (
        aggplane_bench,
        fig3_5_drag,
        fig6_participation,
        fig7_8_hparams,
        fig9_17_byzantine,
        kernels_bench,
        robustness_bench,
        roofline,
        stream_bench,
        sweep_bench,
        telemetry_smoke,
    )

    modules = {
        "fig3_5": fig3_5_drag,
        "fig6": fig6_participation,
        "fig7_8": fig7_8_hparams,
        "fig9_17": fig9_17_byzantine,
        "kernels": kernels_bench,
        "roofline": roofline,
        "stream": stream_bench,
        "robustness": robustness_bench,
        "aggplane": aggplane_bench,
        "sweep": sweep_bench,
        "telemetry": telemetry_smoke,
    }
    selected = args.only.split(",") if args.only else list(modules)
    print("name,us_per_call,derived")
    t0 = time.time()
    for key in selected:
        if key not in modules:
            print(f"# unknown benchmark {key}; have {list(modules)}", file=sys.stderr)
            continue
        print(f"# --- {key} ---", flush=True)
        modules[key].run()
    print(f"# total_wall_s={time.time() - t0:.1f}")


if __name__ == "__main__":
    main()
