"""Quickstart: the D1HT core in five minutes.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core import analysis, build_ring
from repro.core.tuning import EdraParams
from repro.dht import ChurnConfig, run_churn
from repro.kernels.ring_lookup.ops import ring_lookup
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

# 1. A consistent-hashing ring with full routing tables (paper §III)
ring = build_ring(1000, seed=0)
key = "checkpoint/step_420/shard_3"
print(f"owner of {key!r}: peer {ring.owner(key) % 10**6}")

# 2. Self-tuned EDRA parameters (paper §IV-D): every peer derives these
#    locally from the event rate it observes — no coordination.
p = EdraParams.derive(n=10**6, s_avg=174 * 60)
print(f"n=1e6 Gnutella: rho={p.rho} Theta={p.theta:.1f}s "
      f"T_detect={p.t_detect:.1f}s max_buffer={p.max_events:.0f} events")

# 3. Analytical maintenance traffic (paper Eq IV.5) vs the baselines
b = analysis.d1ht_bandwidth(10**6, 174 * 60)
c = analysis.calot_bandwidth(10**6, 174 * 60)
print(f"per-peer maintenance: D1HT={b/1e3:.1f} kbps, 1h-Calot={c/1e3:.1f} "
      f"kbps ({c/b:.0f}x)")

# 4. Protocol-level simulation: >99% one-hop lookups under churn (§VII)
r = run_churn(ChurnConfig(n=200, s_avg=174 * 60, duration=300, warmup=60,
                          protocol="d1ht", seed=1))
print(f"DES n=200: one-hop={r.one_hop_fraction*100:.2f}% "
      f"bandwidth sim/model={r.mean_out_bps/r.analytical_bps:.2f}")

# 5. The serving hot path: batched ring lookups via the Pallas kernel
table = np.sort(np.asarray([i >> 32 for i in ring.ids], np.uint32))
keys = np.random.default_rng(0).integers(0, 2**32, 4096, dtype=np.uint32)
idx = ring_lookup(jnp.asarray(keys), jnp.asarray(table))
print(f"ring_lookup kernel routed {len(keys)} keys; "
      f"first 5 -> peers {np.asarray(idx[:5]).tolist()}")
