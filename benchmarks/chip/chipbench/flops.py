"""Operations and bytes that a piece of work requires, from its shapes.

These are the yardstick behind ``serve.step_mfu`` and the kernels'
roofline shares.  They count what the algorithm needs, not what the
program happens to execute: padding rows, padded prompt tails, masked
cache positions and recomputation are not counted, so a later change
that removes such waste shows as a higher share.

Configurations are dicts with the Hugging Face ``config.json`` keys of a
dense GQA decoder (Qwen2 layout: q/k/v biases, gated SiLU MLP).
"""
from __future__ import annotations

BF16_BYTES = 2


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, hkv, hd, cfg["intermediate_size"], cfg["num_hidden_layers"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, h, hkv, hd, f, _ = _dims(cfg)
    return d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * f


def param_count(cfg: dict) -> int:
    """Every parameter, the tied embedding counted once."""
    d, h, hkv, hd, _, layers = _dims(cfg)
    per_layer = layer_matmul_params(cfg) + (h + 2 * hkv) * hd + 2 * d
    emb = cfg["vocab_size"] * d
    if not cfg.get("tie_word_embeddings", False):
        emb *= 2
    return layers * per_layer + emb + d


def attention_flops(cfg: dict, context: int) -> int:
    """Scores and weighted sum of one query position over ``context``
    cached positions, all layers and heads."""
    _, h, _, hd, _, layers = _dims(cfg)
    return 4 * h * hd * context * layers


def token_flops(cfg: dict, context: int, *, logits: bool) -> int:
    """Forward FLOPs of one token that attends over ``context``
    positions (itself included); ``logits`` adds the output head, which
    a prompt token other than the last does not need."""
    d, *_ = _dims(cfg)
    layers = cfg["num_hidden_layers"]
    n = 2 * layers * layer_matmul_params(cfg) + attention_flops(cfg, context)
    if logits:
        n += 2 * d * cfg["vocab_size"]
    return n


def prefill_flops(cfg: dict, start: int, stop: int, *,
                  last_logits: bool = True) -> int:
    """Prompt positions [start, stop) under the causal mask; the last
    position's logits when ``last_logits``."""
    n = sum(token_flops(cfg, p + 1, logits=False)
            for p in range(start, stop))
    if last_logits and stop > start:
        d = cfg["hidden_size"]
        n += 2 * d * cfg["vocab_size"]
    return n


def decode_flops(cfg: dict, length: int) -> int:
    """One decoded token whose query attends over ``length`` positions."""
    return token_flops(cfg, length, logits=True)


def decode_attention_bytes(cfg: dict, length: int) -> int:
    """HBM bytes one decode-attention row needs over all layers: the K
    and V of ``length`` positions, plus the query read and output
    written (bf16)."""
    _, h, hkv, hd, _, layers = _dims(cfg)
    kv = 2 * length * hkv * hd
    return layers * BF16_BYTES * (kv + 2 * h * hd)


def decode_attention_flops(cfg: dict, length: int) -> int:
    return attention_flops(cfg, length)


def ring_lookup_bytes(keys: int, n: int) -> int:
    """Algorithm-free traffic of one successor lookup call: each 64-bit
    key read and its 64-bit owner written, plus one read of the 8n-byte
    sorted id table.  Independent of the kernel that runs."""
    return 16 * keys + 8 * n


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
