"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled ahead of time against
a described (not attached) v5e, so a kernel the chip's compiler refuses
fails here instead of on the chip.  The topology is described inside a
module fixture — never at import — because only one process at a time
may load the TPU compiler library.  JAX's persistent compilation cache
is off around these compiles: an entry compiled for a described chip
cannot be read back without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

QWEN = get_config("qwen2.5-3b")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args, name=None):
    """Compile for the described chip; ``name`` is the op name the
    kernel's custom call must carry (what a device trace shows)."""
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if name is not None:
        assert re.search(rf"%{name}(\.\d+)? = .*tpu_custom_call", text), name
    return compiled


def test_ring_lookup64_compiles(spec):
    from repro.kernels.ring_lookup.kernel import NAME64, ring_lookup64_pallas
    u32 = jnp.uint32
    _compile(lambda a, b, c, d, n: ring_lookup64_pallas(
        a, b, c, d, n, interpret=False),
        spec((4096,), u32), spec((4096,), u32), spec((1 << 20,), u32),
        spec((1 << 20,), u32), spec((1,), jnp.int32), name=NAME64)


# the ring cells' shapes: a 2^20-key bulk call and a 1,600-key clients
# call on the 32,768-row directory of n = 10^6
@pytest.mark.parametrize("q, nb", [(4096, 8192), (1 << 20, 32768),
                                   (1600, 32768)])
def test_ring_lookup_bucketed_compiles(spec, q, nb):
    from repro.kernels.ring_lookup.kernel import (BW, NAME_BUCKETED,
                                                  ring_lookup_bucketed_pallas)
    u32 = jnp.uint32
    _compile(lambda a, b, c, d, o: ring_lookup_bucketed_pallas(
        a, b, c, d, o, interpret=False),
        spec((q,), u32), spec((q,), u32), spec((nb, BW), u32),
        spec((nb, BW), u32), spec((nb,), jnp.int32), name=NAME_BUCKETED)


# a minor dimension of 2 ((Q, 2) word pairs) is tiled to 128 lanes on the
# TPU, 64 times the bytes, and buffer reuse can hide it from the temp
# total: no array of the lookup program may have one
_MINOR_TWO = re.compile(r"\[(\d+,)*2\]\{")


@pytest.mark.parametrize("q, nb", [(1 << 20, 32768), (1600, 32768)])
def test_ring_lookup_bucketed_words_compiles(spec, q, nb):
    """The one-buffer-each-way lookup program needs no more temporaries
    than the two-word program at the ring cells' shapes, holds no (Q, 2)
    array, and keeps a module name the ring rooflines select."""
    from repro.kernels.ring_lookup.kernel import BW, NAME_BUCKETED
    from repro.kernels.ring_lookup.ops import (ring_lookup_bucketed,
                                               ring_lookup_bucketed_words)
    u32, table = jnp.uint32, (spec((nb, BW), jnp.uint32),
                              spec((nb, BW), jnp.uint32),
                              spec((nb,), jnp.int32))
    words = ring_lookup_bucketed_words.lower(
        spec((2 * q,), u32), *table, interpret=False).compile()
    pair = ring_lookup_bucketed.lower(
        spec((q,), u32), spec((q,), u32), *table, interpret=False).compile()
    text = words.as_text()
    assert text.startswith("HloModule jit_ring_lookup_bucketed_words")
    assert re.search(rf"%{NAME_BUCKETED}(\.\d+)? = .*tpu_custom_call", text)
    assert not _MINOR_TWO.search(text)
    temp = words.memory_analysis().temp_size_in_bytes
    assert temp <= pair.memory_analysis().temp_size_in_bytes + (1 << 20)


def test_ring_lookup64_words_compiles(spec):
    from repro.kernels.ring_lookup.kernel import NAME64
    from repro.kernels.ring_lookup.ops import ring_lookup64_words
    u32 = jnp.uint32
    text = ring_lookup64_words.lower(
        spec((8192,), u32), spec((1 << 20,), u32), spec((1 << 20,), u32),
        spec((1,), jnp.int32), interpret=False).compile().as_text()
    assert text.startswith("HloModule jit_ring_lookup64_words")
    assert re.search(rf"%{NAME64}(\.\d+)? = .*tpu_custom_call", text)
    assert not _MINOR_TWO.search(text)


def test_edra_tree_compiles(spec):
    from repro.kernels.edra_tree.kernel import NAME, edra_tree_pallas
    p, u32 = 1 << 20, jnp.uint32
    _compile(lambda o, n, r, t, k: edra_tree_pallas(
        o, n, r, t, k, levels=20, theta=1.0, delta_avg=0.0625,
        interpret=False),
        spec((p,), u32), spec((p,), u32), spec((p,), u32),
        spec((p,), jnp.float32), spec((p,), u32), name=NAME)


def test_decode_attention_compiles(spec):
    from repro.kernels.decode_attention.kernel import (NAME,
                                                      decode_attention_pallas)
    b, s, hd = 8, 2048, QWEN.resolved_head_dim
    h, hkv, bf16 = QWEN.num_heads, QWEN.num_kv_heads, jnp.bfloat16
    _compile(lambda q, k, v, n: decode_attention_pallas(
        q, k, v, n, interpret=False),
        spec((b, h, hd), bf16), spec((b, s, hkv, hd), bf16),
        spec((b, s, hkv, hd), bf16), spec((b,), jnp.int32), name=NAME)


def test_flash_attention_compiles(spec):
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    s, hd = 2048, QWEN.resolved_head_dim
    h, hkv, bf16 = QWEN.num_heads, QWEN.num_kv_heads, jnp.bfloat16
    _compile(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, interpret=False),
        spec((1, s, h, hd), bf16), spec((1, s, hkv, hd), bf16),
        spec((1, s, hkv, hd), bf16))
