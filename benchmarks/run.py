"""Benchmark harness — one module per paper table/figure + roofline +
the system hot paths (ring lookup, serve plane).

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig7,serve]

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/common.py);
``ring_lookup``, ``serve``, ``maintenance``, ``latency`` and
``placement`` additionally emit BENCH_ring_lookup.json /
BENCH_serve.json / BENCH_maintenance.json / BENCH_latency.json /
BENCH_placement.json so future PRs can track the hot paths.
"""
from __future__ import annotations

import argparse

from repro.launch.compile_cache import enable_compile_cache

from . import (bench_latency, bench_maintenance, bench_placement,
               bench_ring_lookup, bench_serve, bench_tp, fig3_planetlab_bw,
               fig4_hpc_bw, fig5_latency, fig7_analytical, fig8_quarantine,
               roofline, table_validation)
from .common import header

ALL = {
    "fig3": fig3_planetlab_bw.run,
    "fig4": fig4_hpc_bw.run,
    "fig5": fig5_latency.run,
    "fig7": fig7_analytical.run,
    "fig8": fig8_quarantine.run,
    "validation": table_validation.run,
    "roofline": roofline.run,
    "ring_lookup": bench_ring_lookup.run,
    "serve": bench_serve.run,
    "tp": bench_tp.run,
    "maintenance": bench_maintenance.run,
    "latency": bench_latency.run,
    "placement": bench_placement.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slower DES runs)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(ALL))
    args = ap.parse_args()
    names = list(ALL) if not args.only else args.only.split(",")
    enable_compile_cache()
    header()
    for name in names:
        ALL[name](full=args.full)


if __name__ == "__main__":
    main()
