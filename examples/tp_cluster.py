"""Tensor-parallel ServeCluster smoke: one ring node = a 2-device group.

Runs on 8 forced host devices (the env var below must be set before jax
initializes its backend, hence the top-of-file placement): a 4-node
ring over four tp=2 replica groups serves six sessions, survives a
ring-node failure AND a partial-group device loss, and must finish with
every token stream bit-identical to a tp=1 run of the same workload.
Exits nonzero on any divergence — CI's multi-device gate.

Usage: PYTHONPATH=src python examples/tp_cluster.py
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import sys

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.runtime import Membership
from repro.serve import Request, ServeCluster

enable_compile_cache()


def run(model, params, cfg, tp: int) -> tuple:
    m = Membership(t_q=60.0, now=lambda: 0.0)
    for i in range(4):
        m.request_join(f"10.3.0.{i}", 7000 + i)
    cluster = ServeCluster(m, model, params, slots=8, max_len=64, tp=tp)
    rng = np.random.default_rng(0)
    for i in range(6):
        cluster.submit(Request(
            f"s{i}", rng.integers(0, cfg.vocab, 40, dtype=np.int32),
            max_new_tokens=8))
    for _ in range(2):
        cluster.step()
    # churn leg 1: a whole ring node fails -> its sessions re-home via
    # the per-shard KV-block handoff (each device's kv_heads slice is
    # fetched separately and reassembled under the target group)
    m.fail(cluster.sessions["s0"].owner)
    cluster.step()
    if tp > 1:
        # churn leg 2: ONE device of a live group dies -> the whole
        # replica is lost (partial-group policy) and migrates too
        node, devs = next(iter(cluster.supervisor._groups.items()))
        assert cluster.lose_device(devs[-1]) == node
    cluster.run()
    toks = {sid: list(rec.generated)
            for sid, rec in cluster.sessions.items()}
    return toks, cluster.stats()


def main() -> int:
    n = len(jax.devices())
    if n != 8:
        print(f"need 8 host devices, got {n}")
        return 2
    cfg = get_smoke_config("qwen2.5-3b").with_overrides(dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    base, st1 = run(model, params, cfg, tp=1)
    tp2, st2 = run(model, params, cfg, tp=2)
    if tp2 != base:
        print("FAIL: tp=2 token streams diverged from tp=1")
        return 1
    if st2.get("migrated", 0) < 2:
        print(f"FAIL: expected migrations from both churn legs: {st2}")
        return 1
    if st2.get("handoffs", 0) < 1 or st2.get("handoff_misses", 0):
        print(f"FAIL: per-shard KV handoff not exercised cleanly: {st2}")
        return 1
    if st2.get("dead_groups") != 1:
        print(f"FAIL: partial-group loss not recorded: {st2}")
        return 1
    print(f"ok: 6 sessions token-identical tp=1 vs tp=2 through a node "
          f"failure + a partial-group device loss "
          f"(migrated={st2['migrated']}, handoffs={st2['handoffs']}, "
          f"dead_groups={st2['dead_groups']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
