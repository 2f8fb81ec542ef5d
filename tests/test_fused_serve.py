"""Fused route→gather→decode rounds + chunked/overlapped prefill:
dispatch accounting, token identity against the unfused and
whole-prompt paths, trace attribution, and pending-failure safety."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import Model
from repro.runtime import Membership
from repro.serve import Replica, Request, ServeCluster


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_smoke_config("qwen2.5-3b").with_overrides(dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _membership(n, t):
    m = Membership(t_q=60.0, now=lambda: t[0])
    for i in range(n):
        m.request_join(f"10.3.0.{i}", 7000 + i)
    return m


def _requests(cfg, count, *, max_new=8, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(f"s{i}",
                    rng.integers(0, cfg.vocab, 4 + (i % 4) * 3,
                                 dtype=np.int32),
                    max_new_tokens=max_new)
            for i in range(count)]


def _reference_tokens(model, params, prompt, steps, max_len):
    cache = model.init_cache(1, max_len)
    logits, cache = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]}, cache)
    toks = [int(jnp.argmax(logits[0]))]
    dec = jax.jit(model.decode_step)
    length = len(prompt)
    for _ in range(steps - 1):
        logits, cache = dec(params, cache,
                            jnp.asarray([[toks[-1]]], jnp.int32),
                            jnp.asarray([length], jnp.int32))
        toks.append(int(jnp.argmax(logits[0])))
        length += 1
    return toks


def _count_calls(rep, names, counter):
    for name in names:
        orig = getattr(rep, name)

        def wrapped(*a, __orig=orig, __name=name, **kw):
            counter[__name] = counter.get(__name, 0) + 1
            return __orig(*a, **kw)

        setattr(rep, name, wrapped)


# ---------------------------------------------------------------------------
# dispatch accounting: one fused program per round, no host-side routing
# ---------------------------------------------------------------------------

def test_fused_round_is_one_program_and_no_host_lookup(smoke_model):
    """With fusion forced, every replica's decode round must enter the
    device through exactly ONE fused program — never the unfused decode
    pair, never a separate ``RingState.lookup`` dispatch."""
    cfg, model, params = smoke_model
    t = [0.0]
    m = _membership(4, t)
    cluster = ServeCluster(m, model, params, slots=8, max_len=64,
                           fused=True)
    for r in _requests(cfg, 6, max_new=6):
        cluster.submit(r)
    cluster.step()          # warm: traces
    counts = {}
    busy = 0
    for rep in cluster.replicas.values():
        busy += bool(rep.sessions)
        _count_calls(rep, ("_decode_full_fused", "_decode_slots_fused",
                           "_decode_full", "_decode_slots"), counts)

    def no_lookup(*a, **kw):
        raise AssertionError("host-side RingState.lookup during a fused "
                             "decode round")

    cluster.state.lookup = no_lookup
    before = cluster.fused_rounds
    cluster.step()
    del cluster.state.lookup
    fused_calls = counts.get("_decode_full_fused", 0) \
        + counts.get("_decode_slots_fused", 0)
    assert fused_calls == busy          # one fused dispatch per busy replica
    assert counts.get("_decode_full", 0) == 0
    assert counts.get("_decode_slots", 0) == 0
    assert cluster.fused_rounds == before + busy
    assert cluster.fused_routed_keys > 0


def test_fused_tokens_identical_to_unfused(smoke_model):
    """Fusing the route into the decode program must not move a single
    token: same membership, same requests, transcript-for-transcript."""
    cfg, model, params = smoke_model
    outs = {}
    for fused in (True, False):
        t = [0.0]
        cluster = ServeCluster(_membership(4, t), model, params, slots=8,
                               max_len=64, fused=fused)
        for r in _requests(cfg, 6, max_new=8, seed=3):
            cluster.submit(r)
        cluster.run()
        outs[fused] = {sid: list(rec.generated)
                       for sid, rec in cluster.sessions.items()}
    assert outs[True] == outs[False]


def test_fused_rounds_populate_trace_splits(smoke_model):
    """RequestTrace keeps its route/decode split under fusion without a
    calibration: a fused round is one dispatch, so its whole wall time
    is decode time, and ``route_us`` stays the host walk at submit (the
    on-device route is the ``route`` scope of a profiler trace)."""
    cfg, model, params = smoke_model
    t = [0.0]
    cluster = ServeCluster(_membership(4, t), model, params, slots=8,
                           max_len=64, fused=True)
    for r in _requests(cfg, 4, max_new=6, seed=7):
        cluster.submit(r)
    at_submit = {sid: (tr.route_us, tr.decode_us)
                 for sid, tr in cluster.traces.items()}
    cluster.run()
    assert cluster.fused_rounds > 0
    for sid, tr in cluster.traces.items():
        route0, decode0 = at_submit[sid]
        assert tr.done
        assert route0 > 0
        assert tr.route_us == route0          # the submit walk, no more
        assert tr.decode_us > decode0 > 0     # admit, then fused rounds


# ---------------------------------------------------------------------------
# chunked prefill: fixed-shape segments vs whole-prompt, sync and overlapped
# ---------------------------------------------------------------------------

def test_chunked_prefill_matches_whole_prompt(smoke_model):
    """admit() through the fixed-shape segment loop must produce the
    same first token and the same decode stream as the whole-prompt
    prefill (same slab, same positions)."""
    cfg, model, params = smoke_model
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (3, 8, 13, 21)]     # below/at/above chunk multiples
    streams = {}
    for chunk in (8, None):
        rep = Replica(model, slots=4, max_len=48, prefill_chunk=chunk)
        rep.attach_params(params)
        got = {f"c{i}": [rep.admit(Request(f"c{i}", p))]
               for i, p in enumerate(prompts)}
        for _ in range(5):
            for sid, tok in rep.decode_round().items():
                got[sid].append(tok)
        streams[chunk] = got
    assert streams[8] == streams[None]
    for i, p in enumerate(prompts):
        want = _reference_tokens(model, params, p, 6, 48)
        assert streams[8][f"c{i}"] == want


def test_overlapped_prefill_completes_like_sync_admit(smoke_model):
    """begin_admit parks the prefill; advancing it chunk-by-chunk while
    a sibling decodes must yield the sync path's exact tokens, and the
    pending session must stay invisible to decode until it lands."""
    cfg, model, params = smoke_model
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, cfg.vocab, 21, dtype=np.int32)
    sib = rng.integers(0, cfg.vocab, 5, dtype=np.int32)
    rep = Replica(model, slots=4, max_len=48, prefill_chunk=8)
    rep.attach_params(params)
    sib_toks = [rep.admit(Request("sib", sib))]
    assert rep.begin_admit(Request("ovl", prompt)) is None
    assert rep.num_pending == 1 and "ovl" not in rep.sessions
    ovl_toks = []
    while rep.num_pending:
        sib_toks.extend(rep.decode_round().values())   # decode overlaps
        ovl_toks.extend(rep.advance_prefills().values())
    assert len(ovl_toks) == 1
    for _ in range(4):
        for sid, tok in rep.decode_round().items():
            (sib_toks if sid == "sib" else ovl_toks).append(tok)
    assert ovl_toks == _reference_tokens(model, params, prompt, 5, 48)
    assert sib_toks == _reference_tokens(model, params, sib,
                                         len(sib_toks), 48)


def test_failed_pending_prefill_releases_slot_and_spares_siblings(
        smoke_model):
    """One bad pending must not discard a sibling's completion or leak
    its reserved slot (advance_prefills catches per-session)."""
    cfg, model, params = smoke_model
    rng = np.random.default_rng(17)
    rep = Replica(model, slots=4, max_len=48, prefill_chunk=8)
    rep.attach_params(params)
    free0 = rep.num_free
    assert rep.begin_admit(
        Request("good", rng.integers(0, cfg.vocab, 7, dtype=np.int32))) \
        is None
    assert rep.begin_admit(
        Request("bad", rng.integers(0, cfg.vocab, 9, dtype=np.int32))) \
        is None
    rep._pending["bad"]["prompt"] = None       # poison: chunk slice raises
    done = rep.advance_prefills()
    assert "good" in done                      # 7 tokens = one chunk
    assert rep.failed_prefills == ["bad"]
    assert "bad" not in rep._pending and "bad" not in rep.sessions
    assert rep.num_free == free0 - 1           # bad's slot came back
    assert rep.decode_round().keys() == {"good"}


# ---------------------------------------------------------------------------
# overlapped migration end-to-end (fused rounds + chunked re-prefill)
# ---------------------------------------------------------------------------

def test_migration_tokens_identical_under_fused_overlap(smoke_model):
    """Kill an owner mid-decode with fusion + chunked re-prefill on:
    every session must complete with the single-session reference
    stream, straight through the overlapped migration."""
    cfg, model, params = smoke_model
    t = [0.0]
    m = _membership(5, t)
    cluster = ServeCluster(m, model, params, slots=16, max_len=64,
                           fused=True, prefill_chunk=8, prefill_duty=2)
    for r in _requests(cfg, 8, max_new=10, seed=21):
        cluster.submit(r)
    for _ in range(2):
        cluster.step()
    by_owner = {}
    for rec in cluster.sessions.values():
        by_owner.setdefault(rec.owner, []).append(rec)
    victim = max(by_owner, key=lambda o: len(by_owner[o]))
    m.fail(victim)
    assert all(rec.owner != victim for rec in cluster.sessions.values())
    rounds = 0
    while cluster.live_sessions:
        cluster.step()
        rounds += 1
        assert rounds < 128
    assert cluster.pending_migrations == 0
    assert cluster.migrated_sessions >= len(by_owner[victim])
    for rec in cluster.sessions.values():
        want = _reference_tokens(model, params, rec.prompt, 10, 64)
        assert rec.generated == want, f"{rec.session_id} diverged"


def test_failed_overlapped_migration_restrands_and_recovers(smoke_model):
    """A re-prefill that dies mid-chunk must re-strand the session (slot
    released, no phantom) and a later round must re-home it — the
    transcript still completes bit-identical to the reference."""
    cfg, model, params = smoke_model
    t = [0.0]
    m = _membership(5, t)
    cluster = ServeCluster(m, model, params, slots=16, max_len=64,
                           prefill_chunk=8, prefill_duty=1)
    for r in _requests(cfg, 8, max_new=10, seed=23):
        cluster.submit(r)
    cluster.step()
    by_owner = {}
    for rec in cluster.sessions.values():
        by_owner.setdefault(rec.owner, []).append(rec)
    victim = max(by_owner, key=lambda o: len(by_owner[o]))
    m.fail(victim)
    assert cluster.pending_migrations > 0
    sid = next(iter(cluster._pending_homes))
    node = cluster._pending_homes[sid]["node"]
    cluster.replicas[node]._pending[sid]["prompt"] = None    # poison
    rounds = 0
    while cluster.live_sessions:
        cluster.step()
        rounds += 1
        assert rounds < 128
    rec = cluster.sessions[sid]
    assert rec.done and rec.migrations >= 2    # initial + post-failure
    want = _reference_tokens(model, params, rec.prompt, 10, 64)
    assert rec.generated == want

# ---------------------------------------------------------------------------
# repro-lint RL003 regression: greedy argmax lives INSIDE the program
# ---------------------------------------------------------------------------

def test_decode_argmax_is_fused_into_the_program(smoke_model, monkeypatch):
    """Regression (repro-lint RL003): the per-round greedy pick must
    ride inside the compiled decode program — the host sees only the B
    int32 token transfer, never a (B, V) logits readback.  Warm the
    bucket's trace, then poison host-side ``jnp.argmax``: decode rounds
    must keep producing the reference stream without ever calling it."""
    cfg, model, params = smoke_model
    rng = np.random.default_rng(29)
    prompts = [rng.integers(0, cfg.vocab, 5, dtype=np.int32)
               for _ in range(3)]
    rep = Replica(model, slots=4, max_len=48)
    rep.attach_params(params)
    streams = {f"a{i}": [rep.admit(Request(f"a{i}", p))]
               for i, p in enumerate(prompts)}
    for sid, tok in rep.decode_round().items():    # warm this bucket's trace
        streams[sid].append(tok)

    def poisoned(*a, **kw):
        raise AssertionError("host-side jnp.argmax in the decode loop")

    monkeypatch.setattr(jnp, "argmax", poisoned)
    for _ in range(4):
        for sid, tok in rep.decode_round().items():
            streams[sid].append(tok)
    monkeypatch.undo()
    for i, p in enumerate(prompts):
        want = _reference_tokens(model, params, p, 6, 48)
        assert streams[f"a{i}"] == want
