"""Profiler spans and device scopes of the serve loop and the routing
table: a CPU profiler trace of a small fused ServeCluster run (with a
node kill that hands sessions off) and of RingState.apply_events plus
lookups holds every span, nested as the program opens them, with its
metadata; the fused decode program names its parts."""
import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.analysis import spans as S
from repro.configs import get_smoke_config
from repro.core.edra import Event
from repro.core.ringstate import RingState
from repro.models import Model
from repro.runtime import Membership
from repro.serve import Replica, Request, ServeCluster

CHUNK = 8
PREFIXES = ("serve.", "ring.", "kv.")


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_smoke_config("qwen2.5-3b").with_overrides(dtype="float32")
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _record(tmp_path, fn):
    """Run ``fn`` under the profiler; the program's host spans as
    (name, start, end, stats) in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith(PREFIXES))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def _assert_nested(events, child, *parents):
    kids = _named(events, child)
    assert kids, f"no {child} span in the trace"
    outer = [e for p in parents for e in _named(events, p)]
    for k in kids:
        assert _inside(k, outer), f"{child} outside {parents}"


@pytest.fixture(scope="module")
def serve_trace(smoke_model, tmp_path_factory):
    """Fused rounds, submits that export KV chunks, and a kill whose
    sessions move by KV handoff (long prompts) and by an overlapped
    re-prefill (a prompt shorter than one chunk)."""
    cfg, model, params = smoke_model
    t = [0.0]
    m = Membership(t_q=60.0, now=lambda: t[0])
    for i in range(4):
        m.request_join(f"10.6.0.{i}", 7300 + i)
    cluster = ServeCluster(m, model, params, slots=8, max_len=64,
                           prefill_chunk=CHUNK, prefill_duty=1, fused=True)
    rng = np.random.default_rng(5)
    reqs = [Request(f"p{i}", rng.integers(0, cfg.vocab, 5 if i % 2 else 12,
                                          dtype=np.int32), max_new_tokens=6)
            for i in range(12)]
    for r in reqs[:2]:          # compile outside the recorded trace
        cluster.submit(r)
    cluster.step()
    ctx = {}

    def work():
        for r in reqs[2:]:
            cluster.submit(r)
        cluster.step()
        owners = {}
        for rec in cluster.live_sessions:
            owners.setdefault(rec.owner, []).append(rec)
        victim = next(o for o, recs in owners.items()
                      if {len(r.prompt) for r in recs} == {5, 12})
        ctx["moved"] = {r.session_id for r in owners[victim]}
        m.fail(victim)
        cluster.run()

    events = _record(tmp_path_factory.mktemp("serve_trace"), work)
    assert cluster.handoffs >= 1 and cluster.handoff_misses >= 1
    return events, cluster, {r.session_id for r in reqs[2:]}, ctx["moved"]


def test_serve_spans_nest_as_the_round_runs(serve_trace):
    ev, *_ = serve_trace
    _assert_nested(ev, S.SERVE_PREFILL_ADVANCE, S.SERVE_STEP)
    _assert_nested(ev, S.SERVE_DECODE_ROUND, S.SERVE_STEP)
    _assert_nested(ev, S.SERVE_PUSH_TOKENS, S.SERVE_STEP)
    for part in (S.SERVE_UPLOAD_SLOTS, S.SERVE_DISPATCH, S.SERVE_DEVICE_GET):
        _assert_nested(ev, part, S.SERVE_DECODE_ROUND)
    assert any(_inside(e, _named(ev, S.SERVE_STEP))
               for e in _named(ev, S.SERVE_MIGRATE))
    steps = _named(ev, S.SERVE_STEP)
    nums = [e[3]["step_num"] for e in steps]
    assert nums == sorted(nums) and len(set(nums)) == len(nums)
    for e in _named(ev, S.SERVE_DECODE_ROUND):
        assert e[3]["rows"] > 0 and "node" in e[3]


def test_submit_and_handoff_spans_carry_the_session(serve_trace):
    ev, cluster, submitted, moved = serve_trace
    _assert_nested(ev, S.SERVE_ROUTE, S.SERVE_SUBMIT)
    _assert_nested(ev, S.SERVE_ADMIT, S.SERVE_SUBMIT, S.SERVE_HANDOFF)
    _assert_nested(ev, S.KV_EXPORT, S.SERVE_SUBMIT, S.SERVE_STEP,
                   S.SERVE_HANDOFF)
    _assert_nested(ev, S.KV_FETCH, S.SERVE_HANDOFF)
    _assert_nested(ev, S.KV_IMPORT, S.SERVE_HANDOFF)
    _assert_nested(ev, S.SERVE_HANDOFF, S.SERVE_MIGRATE)
    assert {e[3]["session"] for e in _named(ev, S.SERVE_SUBMIT)} == submitted
    handed = {e[3]["session"] for e in _named(ev, S.SERVE_HANDOFF)}
    assert handed == moved
    for e in _named(ev, S.KV_EXPORT):
        assert e[3]["session"] in cluster.sessions and e[3]["chunks"] >= 1
    # an export inside a submit is that request's own
    for sub in _named(ev, S.SERVE_SUBMIT):
        for e in _named(ev, S.KV_EXPORT):
            if _inside(e, [sub]):
                assert e[3]["session"] == sub[3]["session"]
    for imp in _named(ev, S.KV_IMPORT):
        assert imp[3]["session"] in moved and imp[3]["chunks"] >= 1


def test_ring_spans_nest_under_lookup(tmp_path):
    rng = np.random.default_rng(9)
    ids = np.unique(rng.integers(1, 2**63, 4096, dtype=np.uint64))
    state = RingState(ids)
    keys = rng.integers(0, 2**63, 256, dtype=np.uint64)
    state.lookup(keys, use_buckets=False)
    state.lookup(keys, use_buckets=True)     # compile outside the trace

    def work():
        state.apply_events([Event(int(ids[0]), "leave"),
                            Event(int(ids[1]), "leave")])
        state.lookup(keys, use_buckets=False)
        state.lookup(keys, use_buckets=True)

    bytes0 = state.upload_bytes
    ev = _record(tmp_path, work)
    applied, = _named(ev, S.RING_APPLY_EVENTS)
    assert applied[3]["events"] == 2
    lookups = _named(ev, S.RING_LOOKUP)
    assert [e[3]["kernel"] for e in lookups] == ["flat", "bucketed"]
    assert all(e[3]["keys"] == keys.size for e in lookups)
    for part in (S.RING_UPLOAD_KEYS, S.RING_KERNEL, S.RING_OWNERS,
                 S.RING_TABLE_UPLOAD):
        _assert_nested(ev, part, S.RING_LOOKUP)
        assert len(_named(ev, part)) == 2
    flat, bucketed = _named(ev, S.RING_TABLE_UPLOAD)
    assert flat[3]["bytes"] == state.device_capacity * 8 + 4
    # the bucket table re-ships its dirty rows only, as the counter has it
    assert 0 < bucketed[3]["bytes"] < state.bucket_stats()["matrix_bytes"]
    assert flat[3]["bytes"] + bucketed[3]["bytes"] \
        == state.upload_bytes - bytes0


def test_fused_decode_program_names_its_parts(smoke_model):
    _, model, params = smoke_model
    rep = Replica(model, slots=4, max_len=32)
    state = RingState(np.arange(1, 4097, dtype=np.uint64) << np.uint64(50))
    route = state.device_bucket_table()
    u32 = jnp.zeros((4,), jnp.uint32)
    lowered = rep._decode_slots_fused.lower(
        params, rep.cache, jnp.zeros((4, 1), jnp.int32),
        jnp.zeros((4,), jnp.int32), jnp.arange(2, dtype=jnp.int32), u32, u32,
        *route)
    hlo = lowered.compile().as_text()
    assert hlo.startswith("HloModule jit_decode_slots_fused")
    for scope in ("route", "slot_gather", "decode_step", "kv_scatter",
                  "pick"):
        assert f"jit(decode_slots_fused)/{scope}/" in hlo, scope


def test_ringstate_spans_need_no_jax():
    """The routing table's host path opens its spans without jax."""
    code = ("import sys; from repro.core.ringstate import RingState; "
            "s = RingState([3, 9]); "
            "from repro.core.edra import Event; "
            "s.apply_events([Event(5, 'join')]); "
            "assert 'jax' not in sys.modules, 'jax imported'")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in ("src", os.environ.get("PYTHONPATH")) if p))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
