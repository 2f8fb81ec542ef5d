"""Two-level bucketized ring lookup (DESIGN.md §7): equivalence with the
bisect reference under adversarial id distributions, incremental device
maintenance under churn, and O(batches) upload traffic.

The hypothesis property tests skip when hypothesis is absent (the
runtime image bakes in jax + numpy only); the randomized and
deterministic tests below them always run and cover the same invariants
with fixed seeds.
"""
import numpy as np
import pytest

from repro.core.edra import Event
from repro.core.ringstate import _BUCKET_MIN_N, _BUCKET_ROW, RingState

RNG = np.random.default_rng(13)


def _oracle(state: RingState, keys: np.ndarray) -> np.ndarray:
    """bisect over the active view: successor (first id >= key), wrapping
    to the ring origin — the semantics every lookup path must match."""
    act = state.active_ids()
    return act[np.searchsorted(act, keys) % act.size]


def _check_all_paths(state: RingState, keys: np.ndarray) -> None:
    keys = np.asarray(keys, np.uint64)
    want = _oracle(state, keys)
    np.testing.assert_array_equal(
        state.lookup(keys, use_buckets=True), want)
    np.testing.assert_array_equal(
        state.lookup(keys, use_buckets=False), want)
    np.testing.assert_array_equal(state.lookup(keys), want)   # auto


def _boundary_keys(state: RingState) -> np.ndarray:
    """Every active id and both its ring neighbours (wraparound
    included): the exact points where successor ownership flips."""
    act = state.active_ids()
    one = np.uint64(1)
    return np.unique(np.concatenate(
        [act, act - one, act + one,
         np.array([0, 2**64 - 1], np.uint64)]))


def test_row_width_matches_kernel_constant():
    from repro.kernels.ring_lookup.kernel import BW
    assert _BUCKET_ROW == BW


# ---------------------------------------------------------------------------
# hypothesis properties
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HYP = True
except ImportError:                                   # pragma: no cover
    _HYP = False

if _HYP:
    u64 = st.integers(min_value=0, max_value=2**64 - 1)
    u32 = st.integers(min_value=0, max_value=2**32 - 1)

    uniform_ids = st.lists(u64, min_size=2, max_size=300, unique=True)
    # clustered hi-words: many ids share one of a handful of hi words, so
    # whole swaths of the ring land in the same radix partitions
    clustered_ids = st.builds(
        lambda his, los: list({(int(his[i % len(his)]) << 32) | int(l)
                               for i, l in enumerate(los)}),
        st.lists(u32, min_size=1, max_size=3),
        st.lists(u32, min_size=2, max_size=300, unique=True))
    any_ids = st.one_of(uniform_ids, clustered_ids)

    @settings(max_examples=25, deadline=None)
    @given(any_ids, st.lists(u64, min_size=1, max_size=200))
    def test_bucketized_matches_bisect(ids, keys):
        state = RingState(ids)
        _check_all_paths(state, np.array(keys, np.uint64))
        _check_all_paths(state, _boundary_keys(state))

    @settings(max_examples=15, deadline=None)
    @given(any_ids, st.data())
    def test_bucketized_matches_bisect_under_quarantine(ids, data):
        state = RingState(ids)
        masked = data.draw(st.lists(
            st.sampled_from(sorted(ids)), max_size=len(ids) - 1,
            unique=True))
        for pid in masked:
            state.set_quarantined(int(pid), True)
        keys = data.draw(st.lists(u64, min_size=1, max_size=100))
        _check_all_paths(state, np.array(keys, np.uint64))
        _check_all_paths(state, _boundary_keys(state))

    @settings(max_examples=10, deadline=None)
    @given(st.lists(st.tuples(st.lists(u64, min_size=0, max_size=20),
                              st.lists(u64, min_size=0, max_size=20)),
                    min_size=1, max_size=8),
           st.lists(u64, min_size=1, max_size=64))
    def test_churn_sequences_stay_consistent(batches, keys):
        """Randomized join/leave batches interleaved with lookups: every
        sync must land on the bisect answer, and the upload count must
        grow with the number of batches, never with n."""
        state = RingState(_rand_ids(256))
        keys = np.array(keys, np.uint64)
        state.lookup(keys, use_buckets=True)
        u0 = state.upload_count
        for i, (joins, leaves) in enumerate(batches):
            live = state.active_ids()
            evs = [Event(subject_id=int(p), kind="join", seq=i)
                   for p in joins]
            evs += [Event(subject_id=int(live[p % live.size]), kind="leave",
                          seq=i) for p in leaves]
            state.apply_events(evs)
            if len(state):
                _check_all_paths(state, keys)
        assert state.upload_count - u0 <= 3 * len(batches)


    # the word-buffer protocol at the kernel's block edges (BQ = 1024)
    _WORD_QS = (1, 7, 1023, 1025)

    @settings(max_examples=12, deadline=None)
    @given(any_ids, st.sampled_from(_WORD_QS), st.data())
    def test_word_buffer_lookup_matches_bisect_and_word_pairs(ids, q, data):
        """One (2Q,) buffer each way equals the bisect oracle and the
        two-word ``ring_lookup_bucketed`` call, on keys that straddle
        the 32-bit word boundary and on exact ids and id + 1, with a
        quarantined peer."""
        state = RingState(ids)
        state.set_quarantined(int(data.draw(st.sampled_from(sorted(ids)))),
                              True)
        act = state.active_ids()
        special = np.concatenate([
            np.array([0, 2**32 - 1, 2**32, 2**64 - 1], np.uint64),
            act, act + np.uint64(1)])
        rng = np.random.default_rng(data.draw(u32))
        keys = np.resize(np.concatenate(
            [special, rng.integers(0, 2**64, q, dtype=np.uint64)]), q)
        got = state.lookup(keys, use_buckets=True)
        np.testing.assert_array_equal(got, _oracle(state, keys))
        dev = state.device_bucket_table()
        if dev is not None:
            import jax.numpy as jnp

            from repro.kernels.ring_lookup.ops import (
                ring_lookup_bucketed, ring_lookup_bucketed_words)
            words = np.asarray(ring_lookup_bucketed_words(
                jnp.asarray(keys.view("<u4")), *dev))
            ohi, olo = ring_lookup_bucketed(
                jnp.asarray((keys >> np.uint64(32)).astype(np.uint32)),
                jnp.asarray(keys.astype(np.uint32)), *dev)
            np.testing.assert_array_equal(words[1::2], np.asarray(ohi))
            np.testing.assert_array_equal(words[0::2], np.asarray(olo))


# ---------------------------------------------------------------------------
# always-run randomized + deterministic coverage of the same invariants
# ---------------------------------------------------------------------------

def _rand_ids(k: int) -> np.ndarray:
    x = np.unique(RNG.integers(0, 2**64, size=2 * k, dtype=np.uint64))[:k]
    assert x.size == k
    return x


@pytest.mark.parametrize("n", [1, 2, 50, 3000])
def test_forced_bucket_path_matches_bisect(n):
    state = RingState(_rand_ids(n))
    keys = RNG.integers(0, 2**64, size=512, dtype=np.uint64)
    _check_all_paths(state, keys)
    _check_all_paths(state, _boundary_keys(state))


@pytest.mark.parametrize("q", [1, 7, 1023, 1025])
@pytest.mark.parametrize("use_buckets", [True, False])
def test_lookup_returns_q_uint64_owners(q, use_buckets):
    state = RingState(_rand_ids(3000))
    keys = RNG.integers(0, 2**64, size=q, dtype=np.uint64)
    got = state.lookup(keys, use_buckets=use_buckets)
    assert got.shape == (q,) and got.dtype == np.uint64
    np.testing.assert_array_equal(got, _oracle(state, keys))
    assert state.last_lookup_kernel == ("bucketed" if use_buckets else "flat")


def test_lookup_transfers_one_buffer_each_way_per_call():
    """A bucketed call moves its keys up and its owners down as one
    buffer each; membership syncs between calls count elsewhere."""
    state = RingState(_rand_ids(3000))
    keys = RNG.integers(0, 2**64, size=300, dtype=np.uint64)
    assert state.lookup_transfers == {"up": 0, "down": 0}
    for i in range(3):
        state.lookup(keys)
        assert state.lookup_transfers == {"up": i + 1, "down": i + 1}
        live = state.active_ids()
        state.apply_events([Event(subject_id=int(live[i]), kind="leave",
                                  seq=i)])
    stats = state.bucket_stats()
    assert stats["lookup_calls"] == {"bucketed": 3, "flat": 0}
    assert stats["lookup_transfers"] == {"up": 3, "down": 3}


def test_auto_dispatch_threshold():
    small = RingState(_rand_ids(_BUCKET_MIN_N - 1))
    small.lookup(RNG.integers(0, 2**64, size=8, dtype=np.uint64))
    assert not small.bucket_stats().get("enabled", False)
    big = RingState(_rand_ids(_BUCKET_MIN_N))
    big.lookup(RNG.integers(0, 2**64, size=8, dtype=np.uint64))
    assert big.bucket_stats()["valid"]


def test_all_equal_hi_words_fall_back_to_flat():
    """Ids differing only below the radix: no directory size can split
    them, so the index must invalidate and the flat scan must serve."""
    hi = np.uint64(0xDEADBEEF) << np.uint64(32)
    state = RingState(hi | np.arange(1, 4001, dtype=np.uint64))
    keys = np.concatenate([
        RNG.integers(0, 2**64, size=256, dtype=np.uint64),
        hi | np.arange(0, 4100, 7, dtype=np.uint64)])
    _check_all_paths(state, keys)
    assert state.bucket_stats()["valid"] is False


def test_escalation_splits_moderate_clustering():
    """Everything below one base-directory bucket bound, but separable
    with finer radix bits: the directory escalates instead of giving
    up."""
    ids = np.unique(RNG.integers(0, 1 << 58, size=400,
                                 dtype=np.uint64))[:300]
    state = RingState(ids)
    _check_all_paths(state, RNG.integers(0, 2**64, size=256,
                                         dtype=np.uint64))
    stats = state.bucket_stats()
    assert stats["valid"] and stats["buckets"] > 64


def test_quarantined_peers_never_returned():
    state = RingState(_rand_ids(2500))
    live = state.active_ids()
    masked = live[RNG.integers(0, live.size, size=400)]
    for pid in np.unique(masked):
        state.set_quarantined(int(pid), True)
    keys = np.concatenate([_boundary_keys(state),
                           np.asarray(masked, np.uint64)])
    _check_all_paths(state, keys)
    owners = state.lookup(keys, use_buckets=True)
    assert not np.isin(owners, np.unique(masked)).any()


def test_randomized_churn_uploads_scale_with_batches_not_n():
    rng = np.random.default_rng(99)     # local: accounting bounds must
    # not depend on how much of the module RNG earlier tests consumed

    def ids(k):
        return np.unique(rng.integers(0, 2**64, size=2 * k,
                                      dtype=np.uint64))[:k]

    state = RingState(ids(16384))
    keys = rng.integers(0, 2**64, size=300, dtype=np.uint64)
    np.testing.assert_array_equal(state.lookup(keys), _oracle(state, keys))
    u0, b0 = state.upload_count, state.upload_bytes
    batches, events = 12, 16
    row_bytes = _BUCKET_ROW * 8 + 4
    for i in range(batches):
        live = state.active_ids()
        evs = [Event(subject_id=int(p), kind="leave", seq=i)
               for p in live[rng.integers(0, live.size, size=events // 2)]]
        evs += [Event(subject_id=int(p), kind="join", seq=i)
                for p in ids(events // 2)]
        state.apply_events(evs)
        np.testing.assert_array_equal(state.lookup(keys),
                                      _oracle(state, keys))
    # exactly one delta sync per batch...
    assert state.upload_count - u0 == batches
    assert state.delta_uploads >= batches
    # ...each shipping O(events) rows (every event dirties at most its
    # own bucket plus a run of preceding pads), never the O(n) matrix
    stats = state.bucket_stats()
    assert state.upload_bytes - b0 <= batches * 4 * events * row_bytes
    assert state.upload_bytes - b0 < batches * stats["matrix_bytes"] // 8
    _check_all_paths(state, _boundary_keys(state))


def test_delta_sync_equals_full_rebuild():
    """After heavy churn, the scatter-maintained device rows must be
    bit-identical to a from-scratch materialization of the same view,
    with every delta padded to whole chunks of one shape."""
    from repro.core.ringstate import _delta_chunk

    state = RingState(_rand_ids(3000))
    state.lookup(RNG.integers(0, 2**64, size=64, dtype=np.uint64))
    sizes = (40, 1, 7, 25, 3, 60)       # dirty-row counts that pad unevenly
    for i, k in enumerate(sizes):
        live = state.active_ids()
        evs = [Event(subject_id=int(p), kind="leave", seq=i)
               for p in live[RNG.integers(0, live.size, size=k)]]
        evs += [Event(subject_id=int(p), kind="join", seq=i)
                for p in _rand_ids(k)]
        b0 = state.upload_bytes
        state.apply_events(evs)
        state.lookup(RNG.integers(0, 2**64, size=64, dtype=np.uint64))
        chunk_bytes = _delta_chunk(state.bucket_stats()["buckets"]) \
            * (_BUCKET_ROW * 8 + 8)
        assert (state.upload_bytes - b0) % chunk_bytes == 0
    assert state.delta_uploads == len(sizes)
    fresh = RingState(state.active_ids())
    fresh._enable_buckets()
    incr = state.device_bucket_table()
    scratch = fresh.device_bucket_table()
    assert incr is not None and scratch is not None
    if state.bucket_stats()["buckets"] == fresh.bucket_stats()["buckets"]:
        for a, b in zip(incr, scratch):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:   # directory sizes diverged (capacity history): compare answers
        keys = _boundary_keys(state)
        np.testing.assert_array_equal(
            state.lookup(keys, use_buckets=True),
            fresh.lookup(keys, use_buckets=True))


def test_delta_compiles_once_per_directory_size():
    """Messages that dirty 1 to 100+ rows all run one compiled delta
    program per directory size: the chunk shape depends on the
    directory alone."""
    from repro.kernels.ring_lookup.ops import set_bucket_rows

    set_bucket_rows.clear_cache()
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 2**64, size=256, dtype=np.uint64)
    for n, compiled in ((16384, 1), (3000, 2)):
        state = RingState(np.unique(rng.integers(0, 2**64, size=n,
                                                 dtype=np.uint64)))
        state.lookup(keys)
        for i, k in enumerate((1, 8, 40, 100)):
            live = state.active_ids()
            evs = [Event(subject_id=int(p), kind="leave", seq=i)
                   for p in live[rng.integers(0, live.size, size=k)]]
            evs += [Event(subject_id=int(p), kind="join", seq=i)
                    for p in rng.integers(0, 2**64, size=k, dtype=np.uint64)]
            state.apply_events(evs)
            np.testing.assert_array_equal(state.lookup(keys),
                                          _oracle(state, keys))
        assert state.delta_uploads == 4
        assert set_bucket_rows._cache_size() == compiled


@pytest.fixture
def chip_budget(monkeypatch):
    """The compiled-path bucket budget of a 16 GiB device, with the
    kernels still interpreted on this backend."""
    from repro.kernels import backend

    with monkeypatch.context() as m:
        m.setattr(backend, "default_interpret", lambda: False)
        m.setattr(backend, "_device_memory_bytes", lambda: 16 << 30)
        backend.bucket_budget_bytes.cache_clear()
        budget = backend.bucket_budget_bytes()
    backend.bucket_budget_bytes.cache_clear()
    monkeypatch.setattr(backend, "bucket_budget_bytes", lambda: budget)
    return budget


def test_million_peer_table_resolves_through_buckets_on_chip_budget(
        chip_budget):
    """n = 10^6 on a 16 GiB device arms a valid 32,768-bucket index and
    keeps it under EDRA messages: every owner is the successor and no
    call takes the flat scan."""
    assert chip_budget == 1 << 30
    rng = np.random.default_rng(14)
    state = RingState(np.unique(rng.integers(0, 2**64, size=1_000_000,
                                             dtype=np.uint64)))
    keys = rng.integers(0, 2**64, size=512, dtype=np.uint64)
    np.testing.assert_array_equal(state.lookup(keys), _oracle(state, keys))
    for i in range(3):               # messages of 52-53 events, as in D1HT
        live = state.active_ids()
        evs = [Event(subject_id=int(p), kind="leave", seq=i)
               for p in live[rng.integers(0, live.size, size=26)]]
        evs += [Event(subject_id=int(p), kind="join", seq=i)
                for p in rng.integers(0, 2**64, size=27, dtype=np.uint64)]
        state.apply_events(evs)
        np.testing.assert_array_equal(state.lookup(keys),
                                      _oracle(state, keys))
    stats = state.bucket_stats()
    assert stats["valid"] and stats["buckets"] == 32768
    assert stats["max_occupancy"] < _BUCKET_ROW
    assert stats["lookup_calls"] == {"bucketed": 4, "flat": 0}
    assert state.delta_uploads == 3 and state.full_uploads == 1


def test_empty_table_raises_lookup_error():
    with pytest.raises(LookupError, match="empty routing table"):
        RingState().lookup(np.array([1], np.uint64))


def test_flat_kernel_empty_table_raises_lookup_error():
    """Satellite guard: the 32-bit flat kernel surfaces LookupError, not
    a cryptic mod-by-zero, when the table is empty."""
    import jax.numpy as jnp

    from repro.kernels.ring_lookup.kernel import ring_lookup_pallas
    with pytest.raises(LookupError, match="empty routing table"):
        ring_lookup_pallas(jnp.zeros(4, jnp.uint32),
                           jnp.zeros(0, jnp.uint32))
