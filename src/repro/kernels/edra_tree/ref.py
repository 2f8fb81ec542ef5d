"""EDRA dissemination-tree math, generic over numpy / jax.numpy.

One function — ``tree_math`` — is THE definition of the per-(event,
observer) EDRA tree quantities used by the vectorized churn plane
(repro.core.jax_sim.simulate_churn, DESIGN.md §8):

  ttl     acknowledge TTL: rho(n) for the reporter, trailing_zeros(i)
          for offset i > 0  (Rules 3+6+7 — repro.core.edra.ack_ttl)
  depth   hop depth popcount(i)          (repro.core.edra.ack_depth)
  parent  tree parent i & (i-1)          (repro.core.edra.parent_offset)
  ack     absolute acknowledge time: walk the ancestor chain from the
          reporter (prefixes of i's set bits, high to low); each hop
          waits for the SENDER's next Theta-interval boundary (its
          buffer flush, Rules 1-4) then pays an exponential network
          delay.  theta == 0 models an unbuffered protocol (1h-Calot:
          immediate forwarding).
  sends   messages this observer re-emits for the event — the Rule-8
          truncated fan-out #{l < ttl : i + 2^l < n} (Theorem 1 makes
          these sum to n-1 over a full ring).

Interval phases and per-edge delays are derived from counter-based
uint32 hashes (phase keyed on the peer's ring index, delay keyed on
(event, receiver-prefix)), so the tree is a pure function of its
arguments: the Pallas kernel and this reference produce the same
realization, two observers of one event share their ancestors' ack
times, and no (n,)-sized gather is needed at any scale.

``xp`` is the array namespace (numpy or jax.numpy): the Pallas kernel
body calls ``tree_math(jnp, ...)`` on its block refs, the numpy twin
tests call ``tree_math(np, ...)``, and ``edra_tree_ref`` is the jnp
oracle the ops wrapper dispatches to off-TPU.  All integer work is
uint32 (wrap-around semantics identical in numpy and XLA); times are
float32 (quantization ~0.25 ms at a 2000 s horizon — far below Theta).
"""
from __future__ import annotations

import numpy as np

_PHI = np.uint32(0x9E3779B9)
_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
_ONE = np.uint32(1)


def _mix(x):
    """lowbias32 finalizer: uint32 -> well-mixed uint32."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def _h2(a, b):
    """Two-word hash; ``a`` is the stream key, ``b`` the counter."""
    return _mix(a ^ (b * _PHI))


def _u01(xp, h):
    """uint32 hash -> float32 uniform in (0, 1): 24 high bits + half-ulp.
    The 24-bit value goes through int32 (exact: it is below 2^24), since
    the TPU compiler has no uint32 -> float32 cast."""
    return ((h >> 8).astype(xp.int32).astype(xp.float32) + xp.float32(0.5)) \
        * xp.float32(1.0 / (1 << 24))


def _popcount(xp, x):
    """SWAR popcount on uint32 (population_count does not lower in every
    Pallas backend; this is four shifts and a multiply on the VPU)."""
    x = x - ((x >> 1) & np.uint32(0x55555555))
    x = (x & np.uint32(0x33333333)) + ((x >> 2) & np.uint32(0x33333333))
    x = (x + (x >> 4)) & np.uint32(0x0F0F0F0F)
    return ((x * np.uint32(0x01010101)) >> 24).astype(xp.int32)


def tree_math(xp, offset, n, reporter, t_detect, event_key, *,
              levels: int, theta: float, delta_avg: float, seed: int = 0,
              fill_rate: float = 0.0, e_cap: float = 2.0):
    """Per-pair EDRA tree quantities; see module docstring.

    offset/n/reporter/event_key: (P,) uint32; t_detect: (P,) float32.
    ``levels`` must cover every ring: levels >= ceil(log2(max n)).
    Returns (ack f32, ttl i32, depth i32, parent u32, sends i32).

    ``fill_rate`` > 0 enables the Eq IV.4 early-interval-close model: a
    sender also flushes as soon as its buffer reaches ``e_cap`` events
    (every peer acknowledges every event, so acks arrive at the global
    event rate ``fill_rate``).  Per hop the buffered-event count at our
    ack is sampled as Normal(u*E, sqrt(u*E)) — the Poisson count over
    the elapsed interval fraction u — and the flush happens at
    min(grid boundary, time for the remaining e_cap-1-B arrivals).  At
    the paper's design point r*Theta = E this correction vanishes as
    1/sqrt(E); at small n (e_cap ~ 2) it matches the DES, where a
    second buffered event flushes the interval immediately.
    """
    offset = offset.astype(xp.uint32)
    n = n.astype(xp.uint32)
    reporter = reporter.astype(xp.uint32)
    event_key = event_key.astype(xp.uint32)
    zero = np.uint32(0)

    # rho(n) = ceil(log2 n) via bit-smear of n-1 (exact for n >= 2)
    s = n - _ONE
    for sh in (1, 2, 4, 8, 16):
        s = s | (s >> sh)
    rho_n = _popcount(xp, s)
    lsb = offset & (zero - offset)
    ttl = xp.where(offset == zero, rho_n, _popcount(xp, lsb - _ONE))
    depth = _popcount(xp, offset)
    parent = offset & (offset - _ONE)

    phase_key = np.uint32((seed * 0x9E3779B1 + 0x165667B1) & 0xFFFFFFFF)
    e_buf = fill_rate * theta              # mean acks per full interval
    t = t_detect.astype(xp.float32)
    cur = xp.zeros_like(offset)
    for b in reversed(range(levels)):
        bit = ((offset >> b) & _ONE) != zero
        sender = (reporter + cur) % n
        nxt = cur | np.uint32(1 << b)
        h = _h2(event_key, nxt)            # per-(event, edge) stream
        if theta > 0.0:
            # sender forwards at its next interval boundary (Rules 1-4);
            # the 1e-5 nudge keeps a flush-instant ack in the NEXT interval
            # (float32-scaled analogue of jax_sim's 1e-9)
            ph = _u01(xp, _h2(phase_key, sender)) * xp.float32(theta)
            flush = ph + xp.ceil((t - ph) * xp.float32(1.0 / theta)
                                 + xp.float32(1e-5)) * xp.float32(theta)
            if fill_rate > 0.0:            # Eq IV.4 early close
                u = xp.float32(1.0) - (flush - t) * xp.float32(1.0 / theta)
                u = xp.clip(u, xp.float32(0.0), xp.float32(1.0))
                mean_b = u * xp.float32(e_buf)
                z = (_u01(xp, _mix(h ^ np.uint32(0xB5297A4D)))
                     + _u01(xp, _mix(h ^ np.uint32(0x68E31DA4)))
                     + _u01(xp, _mix(h ^ np.uint32(0x1B56C4E9)))
                     - xp.float32(1.5)) * xp.float32(2.0)
                buffered = mean_b + xp.sqrt(mean_b) * z
                need = xp.clip(xp.float32(e_cap - 1.0) - buffered,
                               xp.float32(0.0), None)
                flush = xp.minimum(flush,
                                   t + need * xp.float32(1.0 / fill_rate))
        else:
            flush = t                      # unbuffered (1h-Calot)
        dly = -xp.log(_u01(xp, h)) * xp.float32(delta_avg)
        t = xp.where(bit, flush + dly, t)
        cur = xp.where(bit, nxt, cur)

    sends = xp.zeros_like(depth)
    for l in range(levels):
        fits = (offset + np.uint32(1 << l)) < n         # Rule 8
        sends = sends + xp.where((l < ttl) & fits, 1, 0).astype(xp.int32)
    return t, ttl, depth, parent, sends


def edra_tree_ref(offset, n, reporter, t_detect, event_key, *,
                  levels: int, theta: float, delta_avg: float,
                  seed: int = 0, fill_rate: float = 0.0,
                  e_cap: float = 2.0):
    """jnp oracle with the exact ``tree_math`` semantics (the dispatch
    target off-TPU and the twin the kernel sweeps compare against)."""
    import jax.numpy as jnp

    return tree_math(jnp, offset, n, reporter, t_detect, event_key,
                     levels=levels, theta=theta, delta_avg=delta_avg,
                     seed=seed, fill_rate=fill_rate, e_cap=e_cap)
