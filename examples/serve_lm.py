"""Serving demo: a churn-aware continuous-batching cluster over the
D1HT ring.

Sessions are routed to replicas by single-hop ring lookup; every replica
decodes all its slots at their own cache positions per round; killing a
replica mid-decode migrates exactly its sessions to their replica_set
successors (re-prefilled from the transcript) with zero losses, and a
quarantined spot node proxies requests as a §V gateway without owning
sessions.

    PYTHONPATH=src python examples/serve_lm.py
"""
import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.runtime import Membership
from repro.serve import Request, ServeCluster

enable_compile_cache()

cfg = get_smoke_config("qwen2.5-3b")
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))

membership = Membership(t_q=60.0, now=lambda: 0.0)
for i in range(4):
    membership.request_join(f"10.2.0.{i}", 9000)
cluster = ServeCluster(membership, model, params, slots=8, max_len=64)

# a quarantined spot node: proxies as a gateway, owns nothing (paper §V)
gateway = membership.request_join("10.2.9.9", 9999, preemptible=True)

rng = np.random.default_rng(0)
reqs = [Request(f"user-{i}",
                rng.integers(0, cfg.vocab, 4 + (i % 3) * 4, dtype=np.int32),
                max_new_tokens=8) for i in range(6)]
print("session -> replica routing (single-hop ring lookups, via gateway):")
for r in reqs:
    cluster.submit(r, via=gateway)
    rec = cluster.sessions[r.session_id]
    print(f"  {r.session_id} (prompt {len(r.prompt):>2} tok) "
          f"-> node {rec.owner % 10**6}")
print(f"gateway {gateway % 10**6} proxied {cluster.proxied[gateway]} "
      f"requests, owns {0 if gateway not in cluster.replicas else 1} slabs")

# decode a few rounds, then kill the busiest replica mid-stream
for _ in range(3):
    cluster.step()
busiest = max(cluster.replicas, key=lambda n: cluster.replicas[n].num_active)
print(f"\nkilling node {busiest % 10**6} "
      f"({cluster.replicas[busiest].num_active} active sessions)...")
membership.fail(busiest)
print(f"migrated {cluster.migrated_sessions} sessions to their "
      f"replica_set successors (re-prefilled from transcripts)")

rounds = cluster.run()
print(f"\nall sessions completed ({rounds} more rounds, zero losses):")
for sid, rec in cluster.sessions.items():
    mark = f"  [migrated x{rec.migrations}]" if rec.migrations else ""
    print(f"  {sid}: {rec.generated}{mark}")
