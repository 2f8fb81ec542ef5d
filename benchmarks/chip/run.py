#!/usr/bin/env python3
"""On-chip benchmark of the D1HT ring and its serve plane.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine: set-up
(weights or ring from the seed, every shape warmed), then ``--seconds``
of measured traffic, then the comparison with the plain reference that
decides ``correct``.  The last line of standard output is the result as
one JSON object; the numbers compared, each with its limit, are the last
lines of standard error.  ``--trace 1`` traces a slice of the window and
reports the cell's per-layer metrics instead of its end-to-end ones.
With no TPU, or fewer chips than the cell needs, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()     # set-up counts from the start of the process

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import common  # noqa: E402
from chipbench.common import BenchError, log  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="run the control (a stale table, the fp8 model) "
                    "in the program's place: correct must come out false")
    args = ap.parse_args(argv)
    try:
        bench = common.load_benchmark()
        cell = common.find_cell(bench, args.workload)
        config = common.load_named("configs", cell["config"])
        traffic = common.load_named("traffic", cell["traffic"])
        e2e = common.metrics_for(bench, cell["name"], "end_to_end")
        per_layer = common.metrics_for(bench, cell["name"], "per_layer")
        readers = {m["name"]: common.load_reader(m["name"])
                   for m in per_layer} if args.trace else {}
        driver = common.load_driver(traffic)
        devices = common.require_accelerator(cell["chips"])
    except BenchError as e:
        log(f"run.py: {e}")
        return 2
    # kernels take the committed default tiles: no autotune during set-up
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.devnull
    common.enable_compile_cache()
    out = driver.run(cell=cell, config=config, traffic=traffic,
                     seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devices,
                     control=bool(args.control), t0=T0)
    if args.trace:
        metrics = {}
        for m in per_layer:
            value = readers[m["name"]](out)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        missing = [m["name"] for m in e2e if m["name"] not in out.e2e]
        if missing:
            log(f"run.py: driver gave no {missing}")
            return 3
        metrics = {m["name"]: {"value": float(out.e2e[m["name"]]),
                               "unit": m["unit"]} for m in e2e}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.device.get("memory_peak_bytes")}
    if args.trace and out.trace is not None:
        device["busy_s"] = out.trace.busy_s
        device["window_s"] = out.trace.window_s
    result = {"correct": out.correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if args.trace and out.trace is not None:
        result["breakdown"] = out.trace.breakdown()
    result["checks"] = {c.name: c.as_dict() for c in out.checks}
    print(json.dumps({"notes": out.notes}, default=str), flush=True)
    for c in out.checks:
        log(f"check {c.name}: {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
