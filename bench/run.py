#!/usr/bin/env python3
"""The on-chip benchmark: one cell, one seed, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's inputs from the seed, builds the program and
drives it through its first steps (the first compiles), then the window
measures for ``--seconds``; with ``--trace 1`` a short stretch after
the window is profiled and reduced to the cell's per-layer metrics.
Once the program's state is freed the plain reference recomputes what
the program produced and decides ``correct``.  The last line of
standard output is the result; the checks end standard error.

Everything about a cell is found by name: ``bench/workloads/<cell>.json``
names its configuration (``bench/configs``) and traffic mix
(``bench/traffic/<mix>.json``, whose ``generator`` is a module beside it);
each per-layer metric is a reader ``bench/metrics/<metric>.py``.

    --rehearsal   tiny sizes from the mix, on any device, no device metric
    --control     the reference in bfloat16 stands in for the program
    --fault NAME  the program runs with a fault of ``bench/faults.py`` planted

The limits' readings are runs of this command, one seed to a run, with
and without ``--control`` and ``--fault``: the ``info`` line of stage
``check`` carries every compared number.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def parse(argv=None):
    from bench import faults

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=faults.FAULTS)
    return ap.parse_args(argv)


def load_cell(name: str, rehearsal: bool):
    from bench import harness

    cell = harness.load_json("workloads", f"{name}.json")
    config = harness.load_json("configs", f"{cell['config']}.json")
    mix = harness.load_json("traffic", f"{cell['traffic']}.json")
    small = mix.pop("rehearsal", {})
    if rehearsal:
        config.update(small.pop("config", {}))
        mix.update(small)
        cell = dict(cell, trace_steps=1)
    return cell, config, mix


def main(argv=None) -> int:
    args = parse(argv)
    from bench import faults

    with faults.planted(args.fault) if args.fault else contextlib.nullcontext():
        return run(args)


def run(args) -> int:
    from bench import harness, tracefile, work

    harness.START = T0
    bench = harness.benchmark()
    cell, config, mix = load_cell(args.workload, args.rehearsal)

    import jax
    import jax.numpy as jnp

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    harness.info(stage="jax", devices=len(devices))
    if not args.rehearsal and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        print(f"need {cell['chips']} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 2
    used = devices[: cell["chips"]]

    clock, spans = harness.CompileClock(), harness.Spans()
    gen = harness.load_module("traffic", mix["generator"]).Generator(
        config, mix, args.seed, spans, clock)
    gen.setup()

    # ---- the window
    sent0, lost0 = gen.counts()
    if hasattr(gen, "window_start"):
        gen.window_start()
    spans.total, spans.count = {}, {}
    compiles0 = clock.count
    setup_s = time.perf_counter() - T0
    harness.info(stage="setup", setup_s=setup_s, compile_s=clock.seconds,
                 compiles=clock.count)
    updates, elapsed = harness.drive(gen, args.seconds)
    sent1, lost1 = gen.counts()
    harness.info(stage="window", seconds=elapsed, updates=updates,
                 flushes=updates // gen.k, compiles=clock.count - compiles0)
    e2e = dict(gen.e2e(elapsed, updates), setup_s=setup_s)
    host_spans = {n: spans.mean_us(n) for n in spans.count}

    # ---- the traced stretch
    trace = None
    if args.trace:
        spans.annotate = True
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with tempfile.TemporaryDirectory() as tdir:
            jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(tracefile.WINDOW_SPAN):
                    for _ in range(cell["trace_steps"]):
                        gen.step()
            finally:
                jax.profiler.stop_trace()
            trace = tracefile.Trace(tracefile.load(tdir))
        spans.annotate = False

    stats = [d.memory_stats() or {} for d in used]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    # ---- the check, after the program's state is freed
    gen.free()
    t_ref = time.perf_counter()
    ref = gen.reference(jnp.float32)
    prog = gen.reference(jnp.bfloat16) if args.control else gen.prog
    values = gen.compare(prog, ref)
    harness.info(stage="check", reference_s=time.perf_counter() - t_ref,
                 control=args.control, **values)
    correct, checks = harness.judge(values, cell["limits"])

    metrics = {}
    if not args.rehearsal:
        if trace is None:
            for m in harness.cell_metrics(bench, "end_to_end", args.workload):
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        else:
            run = types.SimpleNamespace(
                trace=trace, config=config, cell=cell, mix=mix, work=gen.work(),
                steps=cell["trace_steps"], spans=host_spans,
                peaks=work.peaks(used[0].device_kind))
            for m in harness.cell_metrics(bench, "per_layer", args.workload):
                v = harness.load_module("metrics", m["name"]).read(run)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": sent1 - sent0, "failed": lost1 - lost0,
            "metrics": metrics, "device": device}
    if trace is not None:
        device.update(busy_s=trace.busy_s(), window_s=trace.window_s)
        line["breakdown"] = {"device_ops": trace.device_ops(), "idle_gaps": trace.idle_gaps()}
    kind = [k for k in ("rehearsal", "control") if getattr(args, k)]
    if args.fault:
        kind.append(f"fault {args.fault}")
    if kind:
        line["run"] = " ".join(kind)
    line["checks"] = checks
    harness.print_checks(checks)
    print(json.dumps(line), flush=True)
    return 0


def use_checkout_cache() -> None:
    """The compile cache at a fixed path inside the checkout, with
    entries of any size kept (the megastep's executable is over 400 MB),
    set before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


if __name__ == "__main__":
    use_checkout_cache()
    sys.exit(main())
