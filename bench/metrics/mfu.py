"""Model FLOP/s utilisation of the window: the operations the forward and
backward passes need per step (`counts.train_flops`, no recomputation),
times the steps completed, over the window and the chip's bf16 peak."""
import counts


def read(rec, ctx):
    if not rec["steps"] or ctx.get("reduced"):
        return None
    cell = ctx["cell"]
    flops = counts.train_flops(ctx["conf"], cell["batch"], cell["seq"])
    peak = counts.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops * len(rec["steps"]) / rec["window_s"] \
        / (peak * ctx["cell"]["chips"])
