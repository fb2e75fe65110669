"""The Pallas ``bitflip`` kernel's share of its roofline: the least time
its bytes and operations allow on the chip, over its device time summed
from the trace.  It corrupts the int8 convolution weights of each unit
once per row and unit run.  Nothing is read when the trace holds no
``bitflip`` operation."""
from bench.flops import arch, bitflip_cost


def read(ctx):
    kernel_s = ctx["trace"]["kernel_s"].get("bitflip", 0.0)
    if kernel_s <= 0.0:
        return None
    m, f, p = ctx["config"]["model"], ctx["config"]["fault"], ctx["peaks"]
    runs = ctx["window"]["layer"]["runs_per_unit"]
    n = sum(r * w for r, w in zip(runs, arch(m["arch"]).unit_conv_weights(
        m["width"])))
    ops, nbytes = bitflip_cost(n, f["faulty_bits"])
    least = max(nbytes / p["hbm_bytes_per_s"], ops / p["int8_ops_per_s"])
    return 100.0 * least / kernel_s
