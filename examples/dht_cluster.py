"""Live D1HT cluster demo: churn, failure detection, elastic re-meshing,
and real bytes surviving node crashes through the replicated data plane.

    PYTHONPATH=src python examples/dht_cluster.py

Exits nonzero if any stored block is lost, torn, or stale after the
induced failures — CI runs this as the data-plane smoke.
"""
import random
import sys

from repro.core.ring import RoutingTable, build_ring
from repro.core.tuning import EdraParams
from repro.dht.d1ht_node import D1HTPeer
from repro.dht.data import BlockStore
from repro.dht.des import LanDelay, SimNet
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import ElasticController, Membership, Placement

enable_compile_cache()

N = 64
net = SimNet(LanDelay(), seed=0)
params = EdraParams.derive(N, 174 * 60)
ids = list(build_ring(N, seed=0).ids)
for pid in ids:
    net.add_peer(D1HTPeer(pid, net, params))
net.ring = RoutingTable(ids)
rng = random.Random(1)
for pid in ids:
    p = net.peers[pid]
    p.table = RoutingTable(ids)
    net.schedule(rng.random() * params.theta, (lambda q: (lambda: q.start()))(p))
net.run_until(30)

# mirror protocol membership into the runtime control plane
membership = Membership()
for pid in ids:
    membership.admit(pid, ("10.0.0.1", 1117))
controller = ElasticController(membership, model_axis=4)
print(f"cluster up: {membership.size()} nodes, "
      f"mesh plan {controller.replan().data_axis}x4")

# replicated data plane over the same ring: put real bytes, 3 copies each
store = BlockStore(membership.ring_state, replication=3)
payloads = {f"demo/block-{i}": bytes(rng.getrandbits(8) for _ in range(256))
            for rng, i in ((random.Random(100 + i), i) for i in range(32))}
for name, value in payloads.items():
    store.put(name, value)
print(f"data plane: {len(payloads)} blocks stored, "
      f"{store.upload_bytes} upload bytes ({store.replication} copies each)")

# crash three nodes; EDRA disseminates, controller re-plans, and the
# data plane re-replicates exactly the blocks each victim held.  The
# victims are ring-ADJACENT (one whole replica group for their arc), so
# repair must run between detections — r simultaneous unrepaired crashes
# of one group is unrecoverable by construction.
repair = {"checked": 0, "repaired": 0, "copied_bytes": 0, "lost": 0}
for victim in ids[10:13]:
    net.peers[victim].stop(crash=True)
    net.ring.remove(victim)
    membership.fail(victim)
    store.drop_node(victim)            # crash destroys the local store
    for k, n in store.sync().items():  # detection-driven re-replication
        repair[k] += n
net.run_until(net.now + 20 * params.theta)
stale = sum(1 for pid in ids[13:20]
            if any(v in net.peers[pid].table for v in ids[10:13]))
plan = controller.plan
print(f"after 3 crashes: peers with stale entries={stale}, "
      f"new plan {plan.data_axis}x{plan.model_axis} "
      f"(dropped {len(plan.dropped)})")

bad = [name for name, value in payloads.items() if store.get(name) != value]
counts = store.replica_counts()
print(f"after 3 crashes: {len(payloads) - len(bad)}/{len(payloads)} blocks "
      f"intact, re-replication checked {repair['checked']} keys, copied "
      f"{repair['copied_bytes']} bytes, min live replicas "
      f"{min(counts.values())}")
if bad or min(counts.values()) < store.replication:
    print(f"DATA LOSS: bad={bad}, counts={counts}")
    sys.exit(1)

placement = Placement(membership.table)
print("placement balance:", placement.balance_stats(2048))
print("expert shards for 32 experts over 4 EP groups:",
      placement.expert_assignment(32, 4).tolist())
