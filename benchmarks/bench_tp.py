"""Tensor-parallel replica-group sweep: tok/s, per-device KV bytes, and
collective bytes per decode round at tp = 1 / 2 / 4.

The sweep runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the flag must be
set before jax initializes), builds one ``TPReplicaGroup`` per tp
degree on the same smoke model, and reports per degree:

  * greedy decode tokens/s for a fixed 4-slot batch (best-of timing of
    the group's fused shard_map decode program);
  * per-device KV-cache bytes (``addressable_shards[0]`` of the sharded
    cache — must scale as 1/TP);
  * collective bytes per decode round from the loop-aware HLO analyzer
    (``launch.hlo_cost.fn_cost``): the psum traffic TP pays per round,
    the roofline's collective term;
  * the full greedy token stream, asserted bit-identical across the
    sweep (exact row/column weight shards + deterministic psum order).

Host-CPU "devices" share one memory bus, so absolute tok/s across tp is
runner noise — the committed numbers are for the BYTES columns and the
identity bit; compare throughput only on real multi-chip hardware.

Usage: PYTHONPATH=src python benchmarks/bench_tp.py
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys

try:
    from .common import emit, provenance
except ImportError:                # standalone: python benchmarks/bench_tp.py
    from common import emit, provenance

CHILD = r"""
import os, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.launch.hlo_cost import fn_cost
from repro.launch.mesh import replica_groups
from repro.models import Model
from repro.models.tp import TPReplicaGroup

REPS = int(os.environ.get("TP_BENCH_REPS", "10"))
cfg = get_smoke_config("qwen2.5-3b").with_overrides(dtype="float32",
                                                    num_kv_heads=4)
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
B, MAXLEN, STEPS = 4, 64, 8
prompt = np.random.default_rng(0).integers(0, cfg.vocab, 12, dtype=np.int32)

rows, streams = [], {}
for tp in (1, 2, 4):
    g = TPReplicaGroup(model, replica_groups(None, tp)[0])
    sp = g.shard_params(params)
    cache = g.init_cache(B, MAXLEN)
    per_dev = g.per_device_cache_bytes(cache)
    prefill, decode_full, _, _ = g.fns()
    toks_b = jnp.tile(jnp.asarray(prompt)[None], (B, 1))
    logits, cache = prefill(sp, {"tokens": toks_b}, cache)
    toks = [int(jnp.argmax(logits[0]))]
    n = jnp.full((B,), len(prompt), jnp.int32)
    for _ in range(STEPS - 1):
        t = jnp.full((B, 1), toks[-1], jnp.int32)
        logits, cache = decode_full(sp, cache, t, n)
        toks.append(int(jnp.argmax(logits[0])))
        n = n + 1
    streams[tp] = toks
    t = jnp.full((B, 1), toks[-1], jnp.int32)
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(decode_full(sp, cache, t, n))
        best = min(best, time.perf_counter() - t0)
    round_us = best * 1e6
    cost = fn_cost(lambda p, c, tt, nn: decode_full(p, c, tt, nn),
                   sp, cache, t, n)
    rows.append({
        "tp": tp,
        "groups": 8 // tp,
        "round_us": round(round_us, 1),
        "tokens_per_s": round(B / (round_us / 1e6), 1),
        "per_device_kv_bytes": int(per_dev),
        "collective_bytes_per_round": int(cost["collective_bytes"]),
        "collective_bytes_by_op": {k: int(v) for k, v in
                                   cost["collective_bytes_by_op"].items()},
    })
base = streams[1]
ident = all(s == base for s in streams.values())
ratios_ok = all(r["per_device_kv_bytes"]
                == rows[0]["per_device_kv_bytes"] // r["tp"] for r in rows)
print("TPBENCH_JSON:" + json.dumps({
    "tokens_identical": ident, "kv_bytes_scale_1_over_tp": ratios_ok,
    "decode_batch": B, "decode_steps": STEPS, "sweep": rows}))
"""


def collect(full: bool = False) -> dict:
    """Run the 8-device sweep in a subprocess and return its payload
    (provenance attached from this process — same backend/mode).

    The forced host devices exist only on the CPU backend.  On an
    accelerator this process already holds the chip and a child could
    not take it, so the sweep is reported as not measured there (the
    4-chip tp path is ``chip_smoke.py --chips 4``)."""
    import jax

    if jax.default_backend() != "cpu":
        return {"sweep": [], "provenance": provenance(),
                "not_measured": "the host-device sweep runs only on the "
                                "CPU backend"}
    env = {**__import__("os").environ, "PYTHONPATH": "src",
           "TP_BENCH_REPS": "20" if full else "10"}
    out = subprocess.run([sys.executable, "-c", CHILD],
                         capture_output=True, text=True, timeout=1200,
                         env=env)
    line = next((ln for ln in out.stdout.splitlines()
                 if ln.startswith("TPBENCH_JSON:")), None)
    if line is None:
        raise RuntimeError(f"tp sweep failed:\n{out.stderr[-4000:]}")
    payload = json.loads(line[len("TPBENCH_JSON:"):])
    payload["provenance"] = provenance()
    assert payload["tokens_identical"], "tp>1 decode diverged from tp=1"
    assert payload["kv_bytes_scale_1_over_tp"], \
        f"per-device KV bytes do not scale 1/TP: {payload['sweep']}"
    return payload


def run(full: bool = False) -> dict:
    payload = collect(full=full)
    for r in payload["sweep"]:
        emit(f"tp_decode_tp{r['tp']}", r["round_us"],
             f"{r['tokens_per_s']:.0f} tok/s, "
             f"kv/dev={r['per_device_kv_bytes']}B, "
             f"coll/round={r['collective_bytes_per_round']}B")
    return payload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default=None,
                    help="optionally write the payload as JSON")
    args = ap.parse_args()
    payload = run(full=args.full)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
