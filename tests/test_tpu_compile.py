"""Compile the Pallas fault kernels for a described TPU v5e at real widths.

Nothing here runs on a chip: each test lowers a kernel for a ``v5e:2x2``
topology described by the installed TPU compiler and checks that Mosaic
accepted it (a ``tpu_custom_call`` in the compiled text).  Interpret-mode
tests cannot see what only the chip's compiler refuses: an unsupported
cast, a misaligned block, too much VMEM.

Widths are olmo-1b's MLP up-projection (d_model 2048 -> d_ff 8192), the
largest contraction the evaluator and the serving engine feed through
``fault_matmul``.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitflip import bitflip_pallas
from repro.kernels.fault_matmul import fault_matmul_pallas
from repro.kernels.quant_bitflip import quant_bitflip_pallas

K, N = 2048, 8192        # olmo-1b d_model, d_ff
M = 256                  # tokens per contraction
ROWS = 4                 # population rows of one vmapped evaluator chunk


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler installed or usable here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described device land in the persistent cache but
    # cannot be read back without a chip; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fault_model", ["flip", "stuck0", "stuck1", "mbu"])
def test_fault_matmul_compiles(one_chip, fault_model):
    def f(x, qw, scale, seed, rate):
        return fault_matmul_pallas(x, qw, scale, seed, rate, 4,
                                   interpret=False, fault_model=fault_model)
    _assert_mosaic(f, _spec((M, K), jnp.float32, one_chip),
                   _spec((K, N), jnp.int8, one_chip),
                   _spec((), jnp.float32, one_chip),
                   _spec((), jnp.int32, one_chip),
                   _spec((), jnp.float32, one_chip))


def test_fault_matmul_vmapped_compiles(one_chip):
    """The evaluator's call: activations and rates batched over the
    population rows, one resident int8 weight and seed shared."""
    def f(x, qw, scale, seed, rates):
        return jax.vmap(
            lambda xr, r: fault_matmul_pallas(xr, qw, scale, seed, r, 4,
                                              interpret=False))(x, rates)
    _assert_mosaic(f, _spec((ROWS, M, K), jnp.float32, one_chip),
                   _spec((K, N), jnp.int8, one_chip),
                   _spec((), jnp.float32, one_chip),
                   _spec((), jnp.int32, one_chip),
                   _spec((ROWS,), jnp.float32, one_chip))


def test_bitflip_int8_compiles(one_chip):
    def f(q, seed, rate):
        return bitflip_pallas(q, seed, rate, 4, interpret=False)
    _assert_mosaic(f, _spec((K, N), jnp.int8, one_chip),
                   _spec((), jnp.int32, one_chip),
                   _spec((), jnp.float32, one_chip))


def test_quant_bitflip_compiles(one_chip):
    def f(x, seed, rate):
        return quant_bitflip_pallas(x, seed, rate, 4, interpret=False)
    _assert_mosaic(f, _spec((K, N), jnp.float32, one_chip),
                   _spec((), jnp.int32, one_chip),
                   _spec((), jnp.float32, one_chip))
