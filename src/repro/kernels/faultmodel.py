"""Shared fault-model math for the Pallas kernels and their oracles.

Everything here is plain ``jnp`` on traced values with plain-int
constants, so the exact same code runs inside a Pallas kernel body
(closure-captured jnp arrays are rejected by ``pallas_call``; literals
are fine) and inside the pure-jnp ``ref.py`` oracles.  Kernel-vs-ref
exactness is then by construction: both sides call ``apply_fault`` with
the same (flat index, seed, rate) triple.

Fault models (``FaultSpec.fault_model``):

  * ``"flip"``   — the paper's Alg. 2: each of the ``faulty_bits`` LSBs
    flips independently with probability ``rate`` (XOR).  Bit plane ``i``
    draws from PRNG plane ``i`` — bit-identical to the historical
    behaviour of these kernels.
  * ``"stuck0"`` / ``"stuck1"`` — per-element stuck-at faults: the same
    per-plane Bernoulli draws select bits, but selected bits are forced
    to 0 (AND-NOT) or 1 (OR) instead of toggled.
  * ``"mbu"``    — multi-bit upset: with probability ``rate`` per
    element, a burst of ``mbu_width`` consecutive bits inside the
    ``faulty_bits`` LSB window flips at once.  The event and the burst
    start position draw from dedicated PRNG planes (``MBU_EVENT_PLANE``,
    ``MBU_POS_PLANE``) so MBU masks are independent of the single-bit
    planes.

The PRNG is the counter-based lowbias32 hash over (seed, flat element
index, plane); rates are traced, so one executable serves every rate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "M1", "M2", "GOLDEN", "INV24",
    "FAULT_MODELS", "MBU_EVENT_PLANE", "MBU_POS_PLANE",
    "lowbias32", "uniform01", "fault_mask", "apply_fault",
]

# Plain ints so Pallas kernels can embed them as literals.
M1 = 0x7FEB352D
M2 = 0x846CA68B
GOLDEN = 0x9E3779B9
INV24 = float(2.0 ** -24)

FAULT_MODELS = ("flip", "stuck0", "stuck1", "mbu")

# PRNG planes for the MBU event/position draws.  Bit planes 0..b-1 are
# taken by the per-bit models; these are far outside that range.
MBU_EVENT_PLANE = 101
MBU_POS_PLANE = 102


def lowbias32(x: jax.Array) -> jax.Array:
    """Bias-minimal 32-bit integer mixer (T. Ettinger's lowbias32)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(M1)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(M2)
    x = x ^ (x >> 16)
    return x


def uniform01(idx: jax.Array, seed: jax.Array, plane: int) -> jax.Array:
    """Uniform float32 in [0,1) with 24-bit resolution for
    (element idx, seed, bit plane).  idx is uint32."""
    h = lowbias32(idx + jnp.uint32(plane * GOLDEN & 0xFFFFFFFF))
    u = lowbias32(h ^ seed.astype(jnp.uint32))
    # Mosaic has no uint32 -> float32 cast; the value is below 2**24, so
    # going through int32 is exact and keeps kernels and refs identical.
    return (u >> 8).astype(jnp.int32).astype(jnp.float32) * INV24


def fault_mask(idx: jax.Array, seed: jax.Array, rate: jax.Array,
               faulty_bits: int, *, fault_model: str = "flip",
               mbu_width: int = 2) -> jax.Array:
    """int32 bit mask of affected bits per element.

    ``idx`` is the uint32 flat element index, ``seed`` a uint32 scalar,
    ``rate`` a traced float32 scalar; ``faulty_bits``/``fault_model``/
    ``mbu_width`` are static.
    """
    if fault_model not in FAULT_MODELS:
        raise ValueError(f"unknown fault_model {fault_model!r}; "
                         f"expected one of {FAULT_MODELS}")
    if fault_model == "mbu":
        width = max(1, min(mbu_width, faulty_bits))
        span = faulty_bits - width + 1          # legal burst start positions
        u_ev = uniform01(idx, seed, MBU_EVENT_PLANE)
        u_pos = uniform01(idx, seed, MBU_POS_PLANE)
        start = jnp.minimum((u_pos * span).astype(jnp.int32), span - 1)
        burst = jnp.left_shift(jnp.int32((1 << width) - 1), start)
        burst = burst & jnp.int32((1 << faulty_bits) - 1)
        return jnp.where(u_ev < rate, burst, 0)
    mask = jnp.zeros(idx.shape, dtype=jnp.int32)
    for i in range(faulty_bits):                # static unroll
        u = uniform01(idx, seed, i)
        mask = mask | jnp.where(u < rate, 1 << i, 0)
    return mask


def apply_fault(q: jax.Array, idx: jax.Array, seed: jax.Array,
                rate: jax.Array, faulty_bits: int, *,
                fault_model: str = "flip", mbu_width: int = 2) -> jax.Array:
    """Corrupt integer tensor ``q`` in-register under the chosen model."""
    if faulty_bits <= 0:
        return q
    mask = fault_mask(idx, seed, rate, faulty_bits,
                      fault_model=fault_model, mbu_width=mbu_width
                      ).astype(q.dtype)
    if fault_model == "stuck0":
        return q & ~mask
    if fault_model == "stuck1":
        return q | mask
    return q ^ mask                             # flip / mbu
