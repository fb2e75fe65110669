"""Plain reference of the ResNet18 search cells: the fc inputs and the
ΔAcc of partitions.

The paper's ResNet18 forward pass in straightforward ``jax.numpy``, with
its fault model written out: every unit quantizes its weights and its
input activations to INT8 (symmetric, per tensor, max|x| -> 127), flips
each of the 4 least significant bits with probability ``rate`` by a
counter-based hash of (seed, flat element index, bit plane), and runs on
the dequantized values.  The rate of a unit is the base rate times the
fault scale of the tier its gene names; its seed is ``base + 7919*unit``
(weights: ``+ 977*leaf`` over the unit's leaves in key order; input
activations: ``+ 1``).  Labels are the clean quantized model's argmax,
so ΔAcc = 1 - the faulty top-1 accuracy.

Weights and images are made here from the configuration's
``weights_seed`` by the same random calls as the model's ``init``; this
file imports nothing of the program.  Computed in float32 at the
highest matmul precision, one row at a time.

Controls, put in the program's place to show that the comparison fails
them: ``dtype="float8_e4m3fn"`` rounds every convolution and matmul
operand to fp8 (e4m3, scaled per tensor), the step below the bfloat16
operands the configuration states; ``dtype="int4"`` quantizes weights
and activations to 4 bits instead of the stated 8 (the same 4 faulty
LSBs); ``faults=False`` sets every flip rate to 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

M1, M2, GOLDEN = 0x7FEB352D, 0x846CA68B, 0x9E3779B9


# --------------------------------------------------------------------------
# fault model
# --------------------------------------------------------------------------
def _mix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(M1)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(M2)
    return x ^ (x >> 16)


def flip_mask(shape, seed, rate, faulty_bits: int):
    """int32 mask of the bits that flip, per element of ``shape``."""
    idx = jnp.arange(int(np.prod(shape)), dtype=jnp.uint32).reshape(shape)
    seed = jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
    mask = jnp.zeros(shape, jnp.int32)
    for b in range(faulty_bits):
        h = _mix(idx + jnp.uint32(b * GOLDEN & 0xFFFFFFFF))
        u = _mix(h ^ seed)
        u = (u >> 8).astype(jnp.int32).astype(jnp.float32) * 2.0 ** -24
        mask = mask | jnp.where(u < rate, 1 << b, 0)
    return mask


def fault(x, seed, rate, bits: int, faulty_bits: int):
    """Quantize, flip, dequantize; returns ``x``'s dtype."""
    qmax = (1 << (bits - 1)) - 1
    amax = jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32),
                       jnp.finfo(jnp.float32).tiny)
    scale = (amax / qmax).astype(jnp.float32)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -qmax - 1, qmax)
    q = q.astype(jnp.int32) ^ flip_mask(x.shape, seed, rate, faulty_bits)
    return (q.astype(jnp.float32) * scale).astype(x.dtype)


# --------------------------------------------------------------------------
# model
# --------------------------------------------------------------------------
def _channels(width):
    return [max(8, int(c * width)) for c in (64, 128, 256, 512)]


def _conv_init(key, kh, kw, cin, cout):
    scale = np.sqrt(2.0 / (kh * kw * cin))
    k1, _ = jax.random.split(key)
    return {"w": jax.random.normal(k1, (kh, kw, cin, cout), jnp.float32)
            * scale, "b": jnp.zeros((cout,), jnp.float32)}


def init(key, width: float, img: int, num_classes: int, n_eval: int):
    """(per-unit params, images) from ``key``, as the model makes them."""
    kp, kx = jax.random.split(key)
    ch = _channels(width)
    ks = jax.random.split(kp, 10)
    units = [{"conv": _conv_init(ks[0], 3, 3, 3, ch[0])}]
    cin, u = ch[0], 1
    for stage, cout in enumerate(ch):
        for blk in range(2):
            kk = jax.random.split(ks[u], 3)
            stride = 2 if (stage > 0 and blk == 0) else 1
            bp = {"c1": _conv_init(kk[0], 3, 3, cin, cout),
                  "c2": _conv_init(kk[1], 3, 3, cout, cout)}
            if stride != 1 or cin != cout:
                bp["proj"] = _conv_init(kk[2], 1, 1, cin, cout)
            units.append(bp)
            cin, u = cout, u + 1
    units.append({"w": jax.random.normal(ks[9], (ch[3], num_classes),
                                         jnp.float32) * np.sqrt(2.0 / ch[3]),
                  "b": jnp.zeros((num_classes,), jnp.float32)})
    x = jax.random.normal(kx, (n_eval, img, img, 3), jnp.float32)
    return units, x


def fp8(x):
    """``x`` rounded to fp8 e4m3, scaled per tensor to e4m3's range."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s


def _unit(i, p, x, w_rate, a_rate, seed, *, n_units, bits, faulty_bits,
          dtype, precision, operand):
    """Unit ``i`` of one row: fault its weights and its input, then run
    it; ``operand`` rounds every contraction operand."""
    def conv(q, h, stride=1):
        return jax.lax.conv_general_dilated(
            operand(h), operand(q["w"]), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=precision) + q["b"]

    s = seed + 7919 * i
    leaves, tree = jax.tree.flatten(p)
    leaves = [fault(w.astype(dtype), s + 977 * j, w_rate, bits, faulty_bits)
              if w.ndim > 1 else w.astype(dtype)
              for j, w in enumerate(leaves)]
    p = jax.tree.unflatten(tree, leaves)
    x = fault(x, s + 1, a_rate, bits, faulty_bits)
    if i == 0:
        return jax.nn.relu(conv(p["conv"], x))
    if i == n_units - 1:
        return jnp.dot(operand(x), operand(p["w"]),
                       precision=precision) + p["b"]
    stage, blk = (i - 1) // 2, (i - 1) % 2
    stride = 2 if (stage > 0 and blk == 0) else 1
    h = jax.nn.relu(conv(p["c1"], x, stride))
    h = conv(p["c2"], h)
    sc = conv(p["proj"], x, stride) if "proj" in p else x
    x = jax.nn.relu(h + sc)
    return x.mean(axis=(1, 2)) if i == n_units - 2 else x


class Reference:
    """The fc inputs and the ΔAcc of rows of tier genes under the
    configuration's faults.  ``dtype`` other than float32, or ``faults``
    off, makes a control (see the module's docstring)."""

    def __init__(self, config: dict, dtype: str = "float32",
                 faults: bool = True):
        m, f = config["model"], config["fault"]
        bits, faulty_bits = f["bits"], f["faulty_bits"]
        if dtype == "int4":
            bits, dtype = 4, "float32"
        scale = np.asarray([t["fault_scale"] for t in config["tiers"]],
                           np.float32) * float(faults)
        self.w_dev = np.asarray(f["weight_fault_rate"] * scale, np.float32)
        self.a_dev = np.asarray(f["act_fault_rate"] * scale, np.float32)
        operand = fp8 if dtype == "float8_e4m3fn" else (lambda v: v)
        dtype = jnp.dtype("float32" if dtype == "float8_e4m3fn" else dtype)
        precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                     else jax.lax.Precision.DEFAULT)
        self.units, self.x = jax.jit(lambda k: init(
            k, m["width"], m["img"], m["num_classes"], config["n_eval"]))(
            jax.random.PRNGKey(config["weights_seed"]))
        n = len(self.units)
        unit = lambda i, p, x, wr, ar, s: _unit(
            i, p, x, wr, ar, s, n_units=n, bits=bits,
            faulty_bits=faulty_bits, dtype=dtype, precision=precision,
            operand=operand)

        def trunk(units, x, wr, ar, seed):
            x = x.astype(dtype)
            for i in range(n - 1):
                x = unit(i, units[i], x, wr[i], ar[i], seed)
            return x.astype(jnp.float32)

        def head(p, feats, wr, ar, seed):
            return unit(n - 1, p, feats.astype(dtype), wr, ar,
                        seed).astype(jnp.float32)

        self._trunk = jax.jit(trunk)
        self._head = jax.jit(jax.vmap(head, in_axes=(None, 0, 0, 0, None)))
        zero = np.zeros(n, np.float32)
        clean = head(self.units[-1], trunk(self.units, self.x, zero, zero,
                                           np.int32(0)), 0.0, 0.0,
                     np.int32(0))
        self.labels = np.asarray(jnp.argmax(clean, axis=-1))

    def features(self, prefixes: np.ndarray, base_seed: int) -> np.ndarray:
        """fc inputs [rows, images, channels] of rows of the first L-1
        genes, one row at a time."""
        return np.stack([np.asarray(self._trunk(
            self.units, self.x, self.w_dev[p], self.a_dev[p],
            np.int32(base_seed))) for p in np.asarray(prefixes)])

    def head_dacc(self, feats: np.ndarray, genes: np.ndarray,
                  base_seed: int, block: int = 256) -> np.ndarray:
        """ΔAcc of the fc unit on given fc inputs, with the last gene of
        each row: 1 - the share of images whose top-1 is the label."""
        out = []
        for j in range(0, len(feats), block):
            g = np.asarray(genes[j:j + block])
            logits = self._head(self.units[-1], feats[j:j + block],
                                self.w_dev[g], self.a_dev[g],
                                np.int32(base_seed))
            pred = np.asarray(jnp.argmax(logits, axis=-1))
            out.append(1.0 - np.mean(pred == self.labels, axis=-1))
        return np.concatenate(out)
