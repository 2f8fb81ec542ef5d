"""The harness, run on the CPU at a size a test can hold with the look
for a chip skipped, finds ``correct`` false when the timed path is
broken underneath: an answer or a token altered where it is produced,
or a step that leaves its state (the table, the KV cache) unchanged;
and the controls (a table one message stale; the fp8 reference in the
model's place) come out not correct."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import common, ring_driver, serve_driver  # noqa: E402

BENCH = common.load_benchmark()


def _ring(**kw):
    import jax

    # a ring under the bucket threshold (the flat scan, as at 10^6) with
    # churn fast enough that a 1.5 s window sees some 20 messages of 15
    # events each: 8 f n / ((16 + 3 rho) rho) events per message
    cfg = dict(common.load_named("configs", "d1ht-internet-1e6"), peers=2000,
               s_avg_s=20, f=0.5)
    tr = dict(common.load_named("traffic", "lookup-clients"), pool=4)
    cell = common.find_cell(BENCH, "ring.lookup.clients")
    return ring_driver.run(cell=cell, config=cfg, traffic=tr, seed=2**32 + 3,
                           seconds=1.5, trace=False, devices=jax.devices(),
                           **kw)


def test_ring_sound_run_is_correct():
    out = _ring()
    assert out.correct, [c.as_dict() for c in out.checks]
    assert out.e2e["lookups_per_s.clients"] > 0 and out.notes["messages_applied"] > 0


def test_ring_stale_table_control_is_caught():
    out = _ring(control=True)
    assert not out.correct
    assert out.checks[0].value > 0


class _AlteredAnswer:
    calls = 0

    def lookup(self, keys, **kw):
        got = super().lookup(keys, **kw)
        type(self).calls += 1
        if type(self).calls == 5:          # one owner, in one call
            got = got.copy()
            got[17] = got[17] ^ np.uint64(1)
        return got


class _StateUnchanged:
    def apply_events(self, events):        # the table never moves
        return 0


@pytest.mark.parametrize("fault", [_AlteredAnswer, _StateUnchanged])
def test_ring_broken_timed_path_is_caught(fault):
    from repro.core.edra import Event
    from repro.core.ringstate import RingState

    broken = type("Broken", (fault, RingState), {})
    out = _ring(program=(Event, broken))
    assert not out.correct
    assert out.checks[0].value >= 1


def _tiny_qwen():
    return dict(common.load_named("configs", "qwen2.5-3b"),
                num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=128, vocab_size=256,
                ring_members=2100,
                # a limit for this size: sound bf16 runs read under 1e-3
                # on the CPU, the fp8 control 0.04-0.08
                limits={"served_logit_gap": 0.02},
                serving=dict(slots=4, max_len=128, prefill_chunk=16,
                             prefill_duty=6, replication=2))


def _serve(control=False, **kw):
    import jax

    tr = dict(common.load_named("traffic", "chat-steady"), rate_per_s=3.0,
              ramp_s=2, session_pool=40000, sample_tokens=40, sample_max=4,
              prompt=dict(mean=20, sigma=0.8, min=4, max=48),
              output=dict(mean=12, sigma=0.6, min=4))
    tr.update(kw)
    cell = common.find_cell(BENCH, "serve.chat.steady")
    return serve_driver.run(cell=cell, config=_tiny_qwen(), traffic=tr,
                            seed=2**31 + 11, seconds=3, trace=False,
                            devices=jax.devices(), control=control)


def test_serve_sound_run_is_correct():
    out = _serve()
    checks = {c.name: c for c in out.checks}
    assert out.correct, {k: c.as_dict() for k, c in checks.items()}
    assert out.notes["sampled_tokens"] >= 40
    assert out.notes["route_checked"] > 0
    for name in ("serve.itl_p50_ms", "serve.ttft_p95_ms"):
        assert common.load_reader(name)(out) > 0


def test_serve_fp8_control_is_caught():
    # the fp8 reference in the program's place fails the comparison that
    # the sound tokens of the same run pass
    out = _serve(control=True)
    checks = {c.name: c for c in out.checks}
    gap = checks["served_logit_gap"]
    assert not out.correct and not gap.ok, gap.as_dict()
    assert gap.value == out.notes["control_widest_gap"]
    assert out.notes["widest_gap"] <= gap.limit
    assert all(c.ok for n, c in checks.items() if n != "served_logit_gap")


def _altered_token(real):
    state = {"n": 0}

    def broken(self, route=None):
        toks = real(self, route=route)
        state["n"] += 1
        if toks and state["n"] % 3 == 0:
            # a token altered where it is produced: the replica's own
            # next input and the cluster's record both carry it
            sid = next(iter(toks))
            bad = (toks[sid] + 1) % self.model.cfg.vocab
            toks[sid] = bad
            self.tokens[self.sessions[sid], 0] = bad
        return toks
    return broken


def _cache_unchanged(real):
    def broken(self, route=None):
        old = self.cache
        toks = real(self, route=route)
        self.cache = old                   # the step's KV writes are lost
        return toks
    return broken


@pytest.mark.parametrize("fault", [_altered_token, _cache_unchanged])
def test_serve_broken_timed_path_is_caught(monkeypatch, fault):
    from repro.serve import server

    monkeypatch.setattr(server.Replica, "decode_round",
                        fault(server.Replica.decode_round))
    out = _serve()
    assert not out.correct
    gap = {c.name: c for c in out.checks}["served_logit_gap"]
    assert not gap.ok, gap.as_dict()


def test_configs_state_their_limits():
    for name in ("qwen2.5-3b", "d1ht-internet-1e6"):
        cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
        assert cfg["limits"]
