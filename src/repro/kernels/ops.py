"""Public jit'd entry points for the fault-injection kernels.

Interpret mode is chosen on every call from the backend: with no TPU
backend (``jax.default_backend() != "tpu"``, e.g. CPU-only CI) the
kernels run in Pallas interpret mode; on a TPU they always lower to
Mosaic.

Fault rates are traced scalars: one executable per (shape, faulty_bits)
serves every rate the optimizer asks for.  Every op has a ``*_ref``
oracle in ``ref.py``; tests sweep shapes/dtypes asserting exact equality.

``fault_matmul`` is the evaluator's in-tile lowering (DESIGN.md "Fault
backends").  On TPU it is the fused ``fault_matmul_pallas`` kernel —
bits flip on the VMEM weight tile right before the MXU, zero extra HBM
traffic.  In interpret mode there is no real tile to fuse into, so it
runs the exact composition instead: the element-wise ``bitflip`` kernel
(bit-identical to ``bitflip_ref``) -> dequantize -> the *same* ``x @ w``
contraction the generic evaluator path uses.  That makes the
``pallas == tables == generic`` backend pin bitwise on CPU CI.  On a
TPU the fused tile accumulates in f32 and agrees with the reference
within a tolerance (``chip_smoke.py`` checks it on the chip).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.bitflip import bitflip_pallas
from repro.kernels.fault_matmul import fault_matmul_pallas
from repro.kernels.quant_bitflip import quant_bitflip_pallas
from repro.quant.fixedpoint import QuantSpec

__all__ = ["bitflip", "quant_bitflip", "fault_matmul"]


def _interpret() -> bool:
    """Interpret mode everywhere but on a TPU backend."""
    return jax.default_backend() != "tpu"


def bitflip(q: jax.Array, seed, fault_rate, faulty_bits: int, *,
            fault_model: str = "flip", mbu_width: int = 2) -> jax.Array:
    """Alg. 2: corrupt the `faulty_bits` LSBs with prob `fault_rate`
    under the chosen fault model (flip / stuck0 / stuck1 / mbu)."""
    if isinstance(fault_rate, (int, float)) and fault_rate <= 0.0:
        return q
    return bitflip_pallas(q, jnp.asarray(seed, jnp.int32),
                          jnp.asarray(fault_rate, jnp.float32),
                          faulty_bits, interpret=_interpret(),
                          fault_model=fault_model, mbu_width=mbu_width)


def quant_bitflip(x: jax.Array, seed, fault_rate, faulty_bits: int,
                  spec: QuantSpec = QuantSpec(), *,
                  fault_model: str = "flip", mbu_width: int = 2) -> jax.Array:
    """Fused quantize -> corrupt -> dequantize on a float tensor."""
    return quant_bitflip_pallas(x, jnp.asarray(seed, jnp.int32),
                                jnp.asarray(fault_rate, jnp.float32),
                                faulty_bits, spec, interpret=_interpret(),
                                fault_model=fault_model, mbu_width=mbu_width)


def fault_matmul(x: jax.Array, qw: jax.Array, scale, seed, fault_rate,
                 faulty_bits: int, *, fault_model: str = "flip",
                 mbu_width: int = 2, out_dtype=None) -> jax.Array:
    """x @ dequant(corrupt(qw)) with zero extra HBM traffic.

    ``out_dtype`` selects the dtype the dequantized weight is cast to
    before the contraction (the original weight dtype); defaults to
    ``x.dtype``.  See the module docstring for the interpret-mode
    dispatch.
    """
    if _interpret():
        qf = bitflip(qw, seed, fault_rate, faulty_bits,
                     fault_model=fault_model, mbu_width=mbu_width)
        w = qf.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)
        return x @ w.astype(out_dtype or x.dtype)
    return fault_matmul_pallas(x, qw, jnp.asarray(scale, jnp.float32),
                               jnp.asarray(seed, jnp.int32),
                               jnp.asarray(fault_rate, jnp.float32),
                               faulty_bits, interpret=False,
                               fault_model=fault_model, mbu_width=mbu_width)


# Re-export oracles for tests/benchmarks.
bitflip_ref = _ref.bitflip_ref
quant_bitflip_ref = _ref.quant_bitflip_ref
fault_matmul_ref = _ref.fault_matmul_ref
