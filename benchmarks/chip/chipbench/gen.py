"""Traffic generators, driven by the parameters in a traffic file.

Every run of a cell draws the same work: the chat arrivals, their sizes
and which of the chip's nodes owns each come from the traffic file's
``base_seed``, the churn-message sizes from the configuration; the run's
``--seed`` draws the token ids, session ids, ring ids, keys and which
peers join and leave.
"""
from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass
from typing import List

import numpy as np

U64 = np.uint64


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """A generator for any whole-number seed (beyond 32 bits too)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) & (2**64 - 1), *salt]))


# ---------------------------------------------------------------------------
# serving: lengths and arrivals
# ---------------------------------------------------------------------------

def lognormal(rng: np.random.Generator, mean: float, sigma: float,
              n: int) -> np.ndarray:
    """Lognormal draws with the given arithmetic mean."""
    mu = math.log(mean) - sigma * sigma / 2
    return rng.lognormal(mu, sigma, n)


def chat_lengths(spec: dict, n: int, max_len: int, rng: np.random.Generator):
    """(prompt, output) token counts, heavy-tailed, clipped so that
    prompt + output <= max_len and each is at least its ``min``."""
    p, o = spec["prompt"], spec["output"]
    prompt = np.rint(lognormal(rng, p["mean"], p["sigma"], n)).astype(int)
    prompt = np.clip(prompt, p["min"], p["max"])
    out = np.rint(lognormal(rng, o["mean"], o["sigma"], n)).astype(int)
    out = np.clip(out, o["min"], max_len - prompt)
    return prompt, out


@dataclass
class Arrival:
    due: float            # seconds after the traffic starts
    prompt_len: int
    output_len: int
    place: float = 0.0    # uniform in [0, 1): which held node owns it


def _arrivals(traffic: dict, span: float, base, offset: float):
    rate = traffic["rate_per_s"]
    n = int(math.ceil(rate * span * 1.5)) + 16
    gaps = base.exponential(1.0 / rate, n)
    due = np.cumsum(gaps)
    k = int(np.searchsorted(due, span))
    prompt, out = chat_lengths(traffic, k, traffic["max_len"], base)
    return [Arrival(float(offset + t), int(p), int(o))
            for t, p, o in zip(due[:k], prompt, out)]


def chat_schedule(traffic: dict, seconds: float, max_len: int) -> List[Arrival]:
    """Poisson arrivals at ``rate_per_s`` with sizes from ``chat_lengths``,
    over the ramp and then the window, drawn from the mix's
    ``base_seed``, each with the ``place`` that picks its owner among
    the chip's nodes.  Every run sends this schedule: the run's seed
    draws the tokens, session ids and weights, not the arrivals nor how
    they split across the replicas (with either drawn per seed, ITL p95
    spread far more across seeds than between two runs of one seed)."""
    traffic = dict(traffic, max_len=max_len)
    base = rng_for(traffic["base_seed"])
    ramp = traffic["ramp_s"]
    out = _arrivals(traffic, ramp, base, 0.0) + \
        _arrivals(traffic, seconds, base, ramp)
    for a, u in zip(out, rng_for(traffic["base_seed"], 1).random(len(out))):
        a.place = float(u)
    return out


def session_keys(seed: int, count: int, start: int = 0):
    """Session ids and their 64-bit ring keys (the serve plane's key:
    SHA-1 of ``session/<id>``, first 8 bytes, big-endian)."""
    sids = [f"s{seed}-{i}" for i in range(start, start + count)]
    keys = np.fromiter(
        (int.from_bytes(hashlib.sha1(f"session/{s}".encode()).digest()[:8],
                        "big") for s in sids), U64, count)
    return sids, keys


# ---------------------------------------------------------------------------
# ring: ids, churn messages, keys
# ---------------------------------------------------------------------------

def ring_ids(n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` distinct uniform 64-bit ids, sorted."""
    ids = np.unique(rng.integers(0, 2**64, n, dtype=U64))
    while ids.size < n:
        ids = np.unique(np.concatenate(
            [ids, rng.integers(0, 2**64, n - ids.size, dtype=U64)]))
    return ids


def event_rate(cfg: dict) -> float:
    """r = 2n / S_avg (D1HT Eq. III.1), events per second."""
    return 2.0 * cfg["peers"] / cfg["s_avg_s"]


def rho(cfg: dict) -> int:
    """rho = ceil(log2 n) (Rule 1): messages a peer receives per Theta."""
    return max(1, math.ceil(math.log2(cfg["peers"])))


def theta(cfg: dict) -> float:
    """Theta = 4 f S_avg / (16 + 3 rho) (Eq. IV.3), seconds."""
    return 4.0 * cfg["f"] * cfg["s_avg_s"] / (16.0 + 3.0 * rho(cfg))


def message_interval(cfg: dict) -> float:
    """A peer receives rho messages per Theta."""
    return theta(cfg) / rho(cfg)


def message_sizes(cfg: dict, count: int) -> np.ndarray:
    """Events carried by each of ``count`` consecutive messages: the
    events that fall due in its interval at rate r, so that the mean is
    exactly r x Theta / rho."""
    r, dt = event_rate(cfg), message_interval(cfg)
    edges = np.floor(r * dt * np.arange(count + 1))
    return np.diff(edges).astype(int)


@dataclass
class Message:
    joins: np.ndarray     # uint64 ids entering
    leaves: np.ndarray    # uint64 ids leaving, graceful or detected crashes


def churn_messages(cfg: dict, ids: np.ndarray, count: int,
                   rng: np.random.Generator) -> List[Message]:
    """``count`` EDRA messages at the configuration's cadence.  Joins and
    leaves alternate so n stays put; leavers are drawn from the initial
    peers, joiners are fresh ids.  A crash reaches the table as a leave
    once detected, so crashes and graceful leaves are one event kind."""
    sizes = message_sizes(cfg, count)
    n_leave = (sizes + 1) // 2
    n_join = sizes - n_leave
    leavers = ids[rng.permutation(ids.size)[:int(n_leave.sum())]]
    fresh = np.setdiff1d(rng.integers(0, 2**64, int(n_join.sum()) + 64,
                                      dtype=U64), ids)
    fresh = fresh[rng.permutation(fresh.size)][:int(n_join.sum())]
    out, li, ji = [], 0, 0
    for nl, nj in zip(n_leave, n_join):
        lv = leavers[li:li + nl]
        out.append(Message(joins=fresh[ji:ji + nj], leaves=lv))
        li += nl
        ji += nj
    return out


def key_batches(spec: dict, ids: np.ndarray, rng: np.random.Generator):
    """``pool`` batches of ``batch`` keys: uniform 64-bit keys, with a
    ``boundary_share`` of them set to exact ids and id + 1 of the
    initial ring, where an off-by-one in the successor shows."""
    out = []
    q = spec["batch"]
    edge = int(q * spec.get("boundary_share", 0.0)) // 2
    for _ in range(spec["pool"]):
        keys = rng.integers(0, 2**64, q, dtype=U64)
        if edge:
            pick = ids[rng.integers(0, ids.size, 2 * edge)]
            keys[:edge] = pick[:edge]
            keys[edge:2 * edge] = pick[edge:] + U64(1)
            keys = keys[rng.permutation(q)]
        out.append(keys)
    return out
