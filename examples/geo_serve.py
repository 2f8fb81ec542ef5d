"""Geo-distributed serving demo: two regions, one churned workload, two
placement policies (DESIGN.md §13).

    PYTHONPATH=src python examples/geo_serve.py

Eight ring nodes are pinned half to "us", half to "eu" (~40 ms one-way
between them); the same request stream — origins alternating between
the regions — and the same two node failures are replayed under
``RingSuccessor`` (placement blind to geography, the pre-policy
behavior) and ``LatencyAware`` (ranks each session's replica set by RTT
from its origin).  Every admission or migration that lands a session
outside its origin region is metered by the serve plane.

Exits 1 unless LatencyAware measurably cuts cross-region placements —
CI runs this as the placement smoke.
"""
import sys

import jax

from repro.configs import get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.runtime import LatencyAware, Membership, RingSuccessor, Topology
from repro.serve import Request, ServeCluster

enable_compile_cache()

N_PER_REGION = 4
REQUESTS = 12
FAIL = 2                      # nodes killed mid-decode, one per region

cfg = get_smoke_config("qwen2.5-3b").with_overrides(dtype="float32")
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))

topo = Topology({"us": (0.0, 0.0), "eu": (40.0, 0.0)})


def run(policy):
    t = [0.0]
    m = Membership(t_q=60.0, now=lambda: t[0], policy=policy)
    by_region = {"us": [], "eu": []}
    for r, region in enumerate(("us", "eu")):
        for i in range(N_PER_REGION):
            nid = m.request_join(f"10.9.{r}.{i}", 7100 + 10 * r + i)
            topo.place(nid, region)
            by_region[region].append(nid)
    cluster = ServeCluster(m, model, params, slots=8, max_len=48)
    import numpy as np
    rng = np.random.default_rng(0)
    for i in range(REQUESTS):
        req = Request(f"g{i}", rng.integers(0, cfg.vocab, 4 + (i % 3) * 5,
                                            dtype=np.int32),
                      max_new_tokens=6)
        cluster.submit(req, origin=("us", "eu")[i % 2])
    for _ in range(2):
        cluster.step()
    # kill one node per region mid-decode: every session it owned gets
    # re-placed by the policy (RingSuccessor -> whatever id sorts next;
    # LatencyAware -> the lowest-RTT surviving replica-set member)
    m.fail(by_region["us"][0])
    m.fail(by_region["eu"][0])
    cluster.run(max_rounds=64)
    s = cluster.stats()
    done = sum(1 for rec in cluster.sessions.values() if rec.done)
    return s, done


base_policy = RingSuccessor(topology=topo)   # topology only for metering
geo_policy = LatencyAware(topo, affinity_ms=5.0)

results = {}
for pol in (base_policy, geo_policy):
    s, done = run(pol)
    cross = s["cross_region_admits"] + s["cross_region_migrations"]
    results[pol.name] = cross
    print(f"{pol.name:>15}: {done}/{REQUESTS} sessions finished, "
          f"{s['migrated']} migrations, cross-region placements: "
          f"{s['cross_region_admits']} admits + "
          f"{s['cross_region_migrations']} migrations = {cross}")
    if done != REQUESTS:
        print(f"FAIL: {pol.name} lost sessions")
        sys.exit(1)

rs, la = results["ring_successor"], results["latency_aware"]
if la >= rs:
    print(f"FAIL: latency_aware did not cut cross-region placements "
          f"({la} vs {rs})")
    sys.exit(1)
print(f"ok: latency_aware cut cross-region placements {rs} -> {la} "
      f"({1 - la / rs:.0%} fewer)")
