"""Plain reference of a Qwen2 decoder (Hugging Face ``Qwen2ForCausalLM``).

Straightforward ``jax.numpy`` in float32 at full matmul precision, no
kernels, no cache, no batching: one causal forward over a whole
sequence.  Layout and equations follow the published model:

    h = x + o_proj(attn(rope(q_proj(rms(x))), rope(k_proj(rms(x))), v_proj(rms(x))))
    y = h + down(silu(gate(rms(h))) * up(rms(h)))
    logits = rms(y_L) @ embed.T              (tied embeddings)

with q/k/v biases, grouped-query attention (``num_key_value_heads``),
rotate-half RoPE at ``rope_theta`` and RMSNorm ``x / rms(x) * w``.

It imports nothing of the program under test.  The weights are the
benchmark's own, made here from the seed (``make_weights``); the
benchmark hands the same arrays to the program in its layout.

``quant="fp8"`` is the control: every linear layer (projections, MLP and
output head) takes float8_e4m3 inputs and weights, scaled per token and
per output channel, the step below the configuration's bfloat16.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(d=d, h=h, hkv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // h,
                f=cfg["intermediate_size"], v=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"])


def weight_shapes(cfg: dict) -> dict:
    k = dims(cfg)
    d, h, hkv, hd, f, L = k["d"], k["h"], k["hkv"], k["hd"], k["f"], k["layers"]
    return {
        "embed": (k["v"], d),
        "ln_f": (d,),
        "ln1": (L, d), "ln2": (L, d),
        "wq": (L, d, h * hd), "bq": (L, h * hd),
        "wk": (L, d, hkv * hd), "bk": (L, hkv * hd),
        "wv": (L, d, hkv * hd), "bv": (L, hkv * hd),
        "wo": (L, h * hd, d),
        "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
    }


def _scale(name: str, shape) -> tuple:
    """(mean, std) of each weight: the published initializer range for
    the embedding, 1/sqrt(fan_in) for matrices, small biases, and norm
    weights near 1 so that a dropped norm shows."""
    if name == "embed":
        return 0.0, 0.02
    if name.startswith("ln"):
        return 1.0, 0.1
    if name.startswith("b"):
        return 0.0, 0.1
    return 0.0, 1.0 / math.sqrt(shape[-2])


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every weight from ``seed``, on the device, in ``dtype``, in one
    jitted call."""
    shapes = weight_shapes(cfg)
    names = sorted(shapes)
    word = np.random.SeedSequence(int(seed) & (2**64 - 1)).generate_state(1)[0]
    key = jax.random.key(int(word) % (2**31 - 1))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            mean, std = _scale(name, shapes[name])
            out[name] = (mean + std * jax.random.normal(
                k, shapes[name], F32)).astype(dtype)
        return out

    return jax.block_until_ready(build(key))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fp8(x, axis):
    """Round to float8_e4m3 with a per-slice absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _linear(x, w, quant):
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _forward(w, tokens, cfg_items, quant):
    cfg = dict(cfg_items)
    k = dims(cfg)
    h, hkv, hd = k["h"], k["hkv"], k["hd"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = tokens.shape[0]
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(F32), lw)
        y = _rms(x, lw["ln1"], eps)
        q = (_linear(y, lw["wq"], quant) + lw["bq"]).reshape(s, h, hd)
        kk = (_linear(y, lw["wk"], quant) + lw["bk"]).reshape(s, hkv, hd)
        v = (_linear(y, lw["wv"], quant) + lw["bv"]).reshape(s, hkv, hd)
        q, kk = _rope(q, theta), _rope(kk, theta)
        kk = jnp.repeat(kk, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, kk,
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, v,
                       precision=jax.lax.Precision.HIGHEST).reshape(s, h * hd)
        x = x + _linear(a, lw["wo"], quant)
        y = _rms(x, lw["ln2"], eps)
        g = _linear(y, lw["w_gate"], quant)
        u = _linear(y, lw["w_up"], quant)
        x = x + _linear(jax.nn.silu(g) * u, lw["w_down"], quant)
        return x, None

    per_layer = {n: w[n] for n in ("ln1", "ln2", "wq", "bq", "wk", "bk", "wv",
                                   "bv", "wo", "w_gate", "w_up", "w_down")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    x = _rms(x, w["ln_f"].astype(F32), eps)
    return x


@partial(jax.jit, static_argnames=("quant",))
def _head_stats(x, embed, targets, quant):
    """Per position: the best logit, the logits of two target token
    rows, and the argmax, without keeping (S, V) beyond this call."""
    logits = _linear(x, embed.astype(F32).T, quant)
    best = jnp.max(logits, -1)
    at = jnp.take_along_axis(logits, targets, axis=-1)
    return best, at, jnp.argmax(logits, -1).astype(jnp.int32)


def pad_to(n: int, least: int = 256) -> int:
    """Next power of two, at least ``least``: few shapes to compile."""
    p = least
    while p < n:
        p *= 2
    return p


def logit_gaps(cfg: dict, w: dict, prompt, served, *, control: bool = False):
    """For a prompt and the tokens a server produced after it, the gap
    by which each served token's reference logit lies below the
    reference's best at that position.  With ``control``, also the gap
    of the token that the fp8 reference puts first at each position.
    Returns (gaps, control_gaps or None) as numpy float64 arrays."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)[:-1]])
    s, p = seq.size, len(prompt)
    padded = np.zeros(pad_to(s), np.int32)
    padded[:s] = seq
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    tok = jnp.asarray(padded)
    targets = np.zeros((pad_to(s), 2), np.int32)
    targets[p - 1:s, 0] = served
    ctrl = None
    if control:
        xq = _forward(w, tok, items, "fp8")
        _, _, top = _head_stats(xq, w["embed"], jnp.asarray(targets), "fp8")
        targets[:, 1] = np.asarray(top)
    x = _forward(w, tok, items, None)
    best, at, _ = _head_stats(x, w["embed"], jnp.asarray(targets), None)
    best = np.asarray(best, np.float64)[p - 1:s]
    at = np.asarray(at, np.float64)[p - 1:s]
    gaps = best - at[:, 0]
    if control:
        ctrl = best - at[:, 1]
    return gaps, ctrl
