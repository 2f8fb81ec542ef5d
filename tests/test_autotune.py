"""Autotune cache behavior + device-memory budget validation.

The search layer is exercised with a fake bench (no accelerator needed):
the contract under test is cache round-tripping, hit-without-research,
and corrupt-file/invalid-entry degradation to the hand-picked defaults.
"""
import json

import pytest

from repro.kernels import autotune
from repro.kernels import backend


@pytest.fixture
def compiled_cache(tmp_path, monkeypatch):
    """Pretend we are on a compiled backend with a private cache file."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    monkeypatch.setattr(autotune, "_is_interpret", lambda: False)
    monkeypatch.setattr(autotune, "_backend_key", lambda: "test:fake-tpu")
    yield path


def _counting_bench(order):
    """bench(tiles) spy: records calls, ranks candidates by ``order``."""
    calls = []

    def bench(tiles):
        calls.append(dict(tiles))
        return float(order(tiles))

    bench.calls = calls
    return bench


def test_search_persists_winner_and_roundtrips(compiled_cache):
    bench = _counting_bench(lambda t: abs(t["bq"] - 512))  # 512 wins
    tiles = autotune.autotune_kernel("ring_lookup_bucketed", {"q": 1000},
                                     bench=bench)
    assert tiles == {"bq": 512}
    assert len(bench.calls) == len(autotune.CANDIDATES["ring_lookup_bucketed"])
    data = json.loads(compiled_cache.read_text())
    assert data["version"] == autotune.CACHE_VERSION
    key = "test:fake-tpu/ring_lookup_bucketed/q1024"  # 1000 -> pow2 bucket
    assert data["entries"][key]["tiles"] == {"bq": 512}
    # resolution consults the same entry (q=1000 and q=1024 share it)
    assert autotune.tiles_for("ring_lookup_bucketed", q=1024) == {"bq": 512}


def test_cache_hit_returns_without_research(compiled_cache):
    first = _counting_bench(lambda t: t["bq"])
    autotune.autotune_kernel("ring_lookup_bucketed", {"q": 512}, bench=first)
    again = _counting_bench(lambda t: t["bq"])
    tiles = autotune.autotune_kernel("ring_lookup_bucketed", {"q": 512},
                                     bench=again)
    assert again.calls == []          # hit: no candidate was ever timed
    assert tiles == {"bq": 256}       # the persisted winner
    forced = _counting_bench(lambda t: -t["bq"])   # force: 2048 wins now
    tiles = autotune.autotune_kernel("ring_lookup_bucketed", {"q": 512},
                                     bench=forced, force=True)
    assert forced.calls != []
    assert tiles == {"bq": 2048}


def test_corrupt_cache_degrades_to_defaults(compiled_cache):
    compiled_cache.write_text("{ not json !!!")
    assert autotune.load_cache() == {"version": autotune.CACHE_VERSION,
                                     "entries": {}}
    assert autotune.tiles_for("ring_lookup", q=1024, n=4096) \
        == autotune.DEFAULTS["ring_lookup"]
    # a search over a corrupt file rewrites it cleanly
    bench = _counting_bench(lambda t: t["bq"] + t["bt"])
    autotune.autotune_kernel("ring_lookup", {"q": 1024, "n": 4096},
                             bench=bench)
    data = json.loads(compiled_cache.read_text())
    assert data["entries"]


def test_wrong_version_cache_ignored(compiled_cache):
    compiled_cache.write_text(json.dumps(
        {"version": 999, "entries": {"x": {"tiles": {"bq": 1}}}}))
    assert autotune.load_cache()["entries"] == {}


def test_invalid_cached_tiles_fall_back(compiled_cache):
    """A stale entry violating the call's divisibility constraint must
    not reach the kernel (decode_attention asserts s % bs == 0)."""
    key = "test:fake-tpu/decode_attention/" + autotune.shape_bucket(s=384)
    autotune._save_cache({"version": autotune.CACHE_VERSION, "entries": {
        key: {"tiles": {"bs": 512}}}})
    assert autotune.tiles_for("decode_attention", s=384) \
        == autotune.DEFAULTS["decode_attention"]


def test_interpret_mode_returns_defaults_without_io(tmp_path, monkeypatch):
    path = tmp_path / "never-created.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    monkeypatch.setattr(autotune, "_is_interpret", lambda: True)
    assert autotune.tiles_for("flash_attention", sq=256, sk=256) \
        == autotune.DEFAULTS["flash_attention"]
    bench = _counting_bench(lambda t: 0.0)
    tiles = autotune.autotune_kernel("flash_attention",
                                     {"sq": 256, "sk": 256}, bench=bench)
    assert tiles == autotune.DEFAULTS["flash_attention"]
    assert bench.calls == []          # no search against the interpreter
    assert not path.exists()          # and no file I/O at all


def test_shape_bucket_rounds_to_pow2():
    assert autotune.shape_bucket(q=1000, n=70_000) == "n131072_q1024"
    assert autotune.shape_bucket(q=1024) == "q1024"
    assert autotune.shape_bucket(s=1) == "s1"


# ---------------------------------------------------------------------------
# bucket_budget_bytes: a share of the device's reported memory on a
# compiled backend, 8 MB where the backend reports none
# ---------------------------------------------------------------------------

@pytest.fixture
def budget_caches():
    backend.bucket_budget_bytes.cache_clear()
    yield
    backend.bucket_budget_bytes.cache_clear()


def test_budget_interpret_mode(budget_caches, monkeypatch):
    monkeypatch.setattr(backend, "default_interpret", lambda: True)
    assert backend.bucket_budget_bytes() == 256 << 20


def test_budget_compiled_unknown_memory(budget_caches, monkeypatch):
    monkeypatch.setattr(backend, "default_interpret", lambda: False)
    monkeypatch.setattr(backend, "_device_memory_bytes", lambda: None)
    assert backend.bucket_budget_bytes() == 8 << 20


def test_budget_capped_by_small_device(budget_caches, monkeypatch):
    monkeypatch.setattr(backend, "default_interpret", lambda: False)
    monkeypatch.setattr(backend, "_device_memory_bytes", lambda: 64 << 20)
    assert backend.bucket_budget_bytes() == 4 << 20      # mem // 16
    backend.bucket_budget_bytes.cache_clear()
    monkeypatch.setattr(backend, "_device_memory_bytes", lambda: 32 << 30)
    assert backend.bucket_budget_bytes() == 2 << 30      # mem // 16
    backend.bucket_budget_bytes.cache_clear()
    monkeypatch.setattr(backend, "_device_memory_bytes", lambda: 4 << 20)
    assert backend.bucket_budget_bytes() == 1 << 20      # floor


def test_budget_reads_memory_stats_from_device(budget_caches, monkeypatch):
    """End-to-end through _device_memory_bytes with a fake jax device."""
    import jax

    class FakeDev:
        def memory_stats(self):
            return {"bytes_limit": 128 << 20}

    monkeypatch.setattr(backend, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeDev()])
    assert backend.bucket_budget_bytes() == 8 << 20      # 128MB/16 = 8MB
    backend.bucket_budget_bytes.cache_clear()

    class TinyDev:
        def memory_stats(self):
            return {"bytes_reservable_limit": 48 << 20}

    monkeypatch.setattr(jax, "devices", lambda *a: [TinyDev()])
    assert backend.bucket_budget_bytes() == 3 << 20


def test_search_raises_when_every_candidate_is_refused(compiled_cache):
    """A sweep in which the compiler refuses every tile means the kernel
    cannot run on this backend: that must raise, not read as defaults."""
    def refuse(tiles):
        raise ValueError(f"refused {tiles}")

    with pytest.raises(RuntimeError, match="refused every candidate"):
        autotune.autotune_kernel("decode_attention", {"s": 2048},
                                 bench=refuse)
    assert not compiled_cache.exists()


def test_default_cache_lives_in_the_checkout(monkeypatch):
    from pathlib import Path

    monkeypatch.delenv(autotune.CACHE_ENV, raising=False)
    root = Path(autotune.__file__).resolve().parents[3]
    assert Path(autotune.cache_path()) == root / ".autotune" / "autotune.json"
    monkeypatch.setenv(autotune.CACHE_ENV, "/elsewhere/at.json")
    assert autotune.cache_path() == "/elsewhere/at.json"


@pytest.mark.parametrize("platform, stats, expect", [
    ("cpu", RuntimeError("no stats"), None),
    ("cpu", None, None),
    ("tpu", {"bytes_limit": 16 << 30}, 16 << 30),
    ("tpu", RuntimeError("no stats"), RuntimeError),
    ("tpu", {}, RuntimeError),
])
def test_device_memory_never_hides_a_tpu(monkeypatch, platform, stats, expect):
    """Off a TPU, missing memory stats read as unknown; on a TPU they are
    an error, not a silent fallback to the 8 MB budget."""
    import jax

    class Dev:
        def memory_stats(self):
            if isinstance(stats, Exception):
                raise stats
            return stats

    Dev.platform = platform
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    if expect is RuntimeError:
        with pytest.raises(RuntimeError):
            backend._device_memory_bytes()
    else:
        assert backend._device_memory_bytes() == expect
