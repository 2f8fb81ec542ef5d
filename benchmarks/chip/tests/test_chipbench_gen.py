"""Traffic generators: seeds, clipping, the chip's share of the ring and
the EDRA message cadence."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

from chipbench import common, gen  # noqa: E402

STEADY = json.loads((HERE / "traffic" / "chat-steady.json").read_text())
RING = json.loads((HERE / "configs" / "d1ht-internet-1e6.json").read_text())
successor = common.load_reference("successor").successor


def test_every_run_sends_one_schedule():
    a = gen.chat_schedule(STEADY, 51, 2048)
    b = gen.chat_schedule(STEADY, 51, 2048)
    assert [(x.due, x.prompt_len, x.output_len) for x in a] == \
        [(x.due, x.prompt_len, x.output_len) for x in b]
    ramp = [x for x in a if x.due < STEADY["ramp_s"]]
    window = [x for x in a if x.due >= STEADY["ramp_s"]]
    assert ramp and window and a[-1].due < STEADY["ramp_s"] + 51
    dues = [x.due for x in a]
    assert dues == sorted(dues)


def test_same_seed_same_inputs():
    a, b = gen.session_keys(2**33 + 1, 8), gen.session_keys(2**33 + 1, 8)
    c = gen.session_keys(7, 8)
    assert a[0] == b[0] and (a[1] == b[1]).all() and a[0] != c[0]
    x = gen.rng_for(2**40 + 3, 2).integers(0, 151936, 64)
    y = gen.rng_for(2**40 + 3, 2).integers(0, 151936, 64)
    assert (x == y).all()


def test_lengths_clip_to_max_len():
    rng = gen.rng_for(3)
    spec = dict(STEADY, prompt=dict(mean=900, sigma=1.5, min=4, max=1024),
                output=dict(mean=1500, sigma=1.5, min=4))
    p, o = gen.chat_lengths(spec, 5000, 2048, rng)
    assert (p >= 4).all() and (p <= 1024).all()
    assert (o >= 4).all() and (p + o <= 2048).all()
    p, o = gen.chat_lengths(STEADY, 20000, 2048, gen.rng_for(4))
    assert 120 < p.mean() < 200 and 250 < o.mean() < 420


def test_arrival_rate():
    s = gen.chat_schedule(dict(STEADY, rate_per_s=5.0, ramp_s=0), 400, 2048)
    assert 5.0 * 400 * 0.9 < len(s) < 5.0 * 400 * 1.1


def test_session_keys_are_the_serve_plane_keys():
    sys.path.insert(0, str(ROOT / "src"))
    from repro.serve.server import session_key

    sids, keys = gen.session_keys(11, 50)
    assert [session_key(s) for s in sids] == [int(k) for k in keys]


def test_chip_share_picks_agree_with_the_successor_oracle():
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.ringstate import RingState
    from chipbench.serve_driver import held_pair

    rng = gen.rng_for(5)
    ring = gen.ring_ids(3000, rng)
    _, keys = gen.session_keys(5, 20000)
    held, share0 = held_pair(ring)
    i = int(np.searchsorted(ring, np.uint64(held[0])))
    assert ring[(i + 1) % ring.size] == held[1]       # consecutive
    own = successor(ring, keys)
    state = RingState(ring)
    picked = keys[np.isin(own, np.array(held, np.uint64))]
    assert picked.size > 0
    assert all(state.owner(int(k)) in held for k in picked[:200])
    # the pair's smaller arc is the largest of any consecutive pair, and
    # the first node's share of the keys follows the arcs
    arc = [(int(ring[k]) - int(ring[k - 1])) % 2**64 for k in range(ring.size)]
    j = (i + 1) % ring.size
    assert min(arc[i], arc[j]) == max(min(arc[k], arc[(k + 1) % ring.size])
                                      for k in range(ring.size))
    assert share0 == pytest.approx(arc[i] / (arc[i] + arc[j]))
    got = np.mean(own[np.isin(own, np.array(held, np.uint64))] == held[0])
    assert abs(got - share0) < 0.15


def test_every_seed_splits_the_schedule_alike():
    a = gen.chat_schedule(STEADY, 51, 2048)
    places = [x.place for x in a]
    assert all(0.0 <= u < 1.0 for u in places)
    assert places == [x.place for x in gen.chat_schedule(STEADY, 51, 2048)]
    # the places come from a stream of their own: the arrivals and sizes
    # are those of the schedule without them
    base = gen.rng_for(STEADY["base_seed"])
    first = gen._arrivals(dict(STEADY, max_len=2048), STEADY["ramp_s"], base, 0.0)
    assert [(x.due, x.prompt_len) for x in first] == \
        [(x.due, x.prompt_len) for x in a[:len(first)]]


def test_ring_message_cadence():
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import tuning

    r = gen.event_rate(RING)
    assert r == pytest.approx(2 * 10**6 / (174 * 60))
    assert gen.rho(RING) == 20 == tuning.rho(10**6)
    assert gen.theta(RING) == pytest.approx(tuning.theta(10**6, 174 * 60))
    dt = gen.message_interval(RING)
    assert dt == pytest.approx(gen.theta(RING) / gen.rho(RING))
    sizes = gen.message_sizes(RING, 2000)
    # events per second over the messages equals r, per message r*Theta/rho
    assert sizes.sum() / (2000 * dt) == pytest.approx(r, rel=1e-3)
    assert set(np.unique(sizes)) <= {52, 53}


def test_churn_messages_keep_n_and_never_reuse_ids():
    rng = gen.rng_for(9)
    cfg = dict(RING, peers=5000)
    ids = gen.ring_ids(5000, rng)
    msgs = gen.churn_messages(cfg, ids, 40, rng)
    leaves = np.concatenate([m.leaves for m in msgs])
    joins = np.concatenate([m.joins for m in msgs])
    assert np.unique(leaves).size == leaves.size and np.isin(leaves, ids).all()
    assert not np.isin(joins, ids).any()
    assert abs(leaves.size - joins.size) <= len(msgs)


def test_key_batches_hold_boundary_keys():
    rng = gen.rng_for(1)
    ids = gen.ring_ids(1000, rng)
    spec = {"batch": 1024, "pool": 2, "boundary_share": 1 / 64}
    b = gen.key_batches(spec, ids, rng)
    assert len(b) == 2 and b[0].size == 1024
    assert np.isin(b[0], ids).sum() >= 8
    assert np.isin(b[0] - np.uint64(1), ids).sum() >= 8
