"""Model FLOPs of the unit runs the engine performed in the traced window
(convolutions and fc of each unit run, per calibration image, padding
rows not counted), as a share of the chip's bf16 peak over the window."""
from bench.flops import arch


def read(ctx):
    cfg = ctx["config"]
    m = cfg["model"]
    per_image = arch(m["arch"]).unit_flops(m["width"], m["img"],
                                           m["num_classes"])
    runs = ctx["window"]["layer"]["runs_per_unit"]
    flops = sum(r * f for r, f in zip(runs, per_image)) * cfg["n_eval"]
    t = ctx["trace"]
    return 100.0 * flops / (t["window_s"] * ctx["peaks"]["bf16_flops_per_s"])
