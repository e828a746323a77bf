#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It finds the cell in BENCHMARK.json, the cell's configuration,
traffic mix and per-layer readers by their names, drives the program for
``--seconds`` seconds after set-up, checks what the timed path produced
against the plain reference, and prints one JSON object as its last line.
No TPU, or fewer chips than the cell asks for, is a non-zero exit and no
result.
"""

from __future__ import annotations

import time

_T_IMPORT = time.time()

import argparse
import os
import sys
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def process_start() -> float:
    """Wall-clock time at which this process was created."""
    try:
        import psutil

        return psutil.Process().create_time()
    except Exception:  # no psutil: the interpreter's first line stands in
        return _T_IMPORT


def main(argv=None, skip_device_check: bool = False) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--root", default=ROOT, help=argparse.SUPPRESS)
    args = parser.parse_args(argv)

    from benchmark.harness import manifest as mf
    from benchmark.harness import peaks, result

    manifest = mf.Manifest(args.root)
    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    mix = manifest.traffic(cell["traffic"])
    seconds = float(args.seconds if args.seconds is not None
                    else manifest.data["run_seconds"])
    run = result.Run(cell, config, mix, args.seed, seconds, bool(args.trace))
    run.counters["process_start"] = process_start()

    import jax

    devices = jax.devices()
    run.device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
    if not skip_device_check:
        if devices[0].platform != "tpu":
            print(f"no TPU: JAX found {run.device}; the benchmark has no "
                  "CPU fallback", file=sys.stderr)
            return 3
        if len(devices) < cell["chips"]:
            print(f"cell {cell['name']} needs {cell['chips']} chips, JAX "
                  f"found {len(devices)}", file=sys.stderr)
            return 3
        run.peak = peaks.peak(devices[0].device_kind)
    run.mark("imports and devices")
    run.device["count"] = cell["chips"]
    run.devices = devices[:cell["chips"]]

    drive = mf.driver(mix["kind"])
    drive(run, manifest)

    wanted = (manifest.per_layer(cell["name"]) if run.trace_on
              else manifest.end_to_end(cell["name"]))
    metrics = {}
    for entry in wanted:
        name = entry["name"]
        if run.trace_on:
            value = manifest.reader(name)(run)
        else:
            value = run.end_to_end.get(name)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": entry["unit"]}
    breakdown = None
    if run.trace is not None:
        from benchmark.harness import xplane

        breakdown = xplane.breakdown(run.trace)
        run.device["busy_s"] = run.trace.busy_s
        run.device["window_s"] = run.trace.window_s
    result.emit(run, metrics, breakdown)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    sys.exit(code)
