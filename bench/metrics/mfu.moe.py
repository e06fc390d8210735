"""Model FLOP/s utilisation of a sparse-expert cell: `mfu`'s arithmetic
with the operations from the configuration's own reference
(`train_flops`: active weights with the held experts at their expected
share, windowed and full attention pairs, no recomputation)."""
import counts
import harness


def read(rec, ctx):
    if not rec["steps"] or ctx.get("reduced"):
        return None
    ref = harness.load_module("reference", ctx["conf"]["reference"])
    if not hasattr(ref, "train_flops"):
        return None
    cell = ctx["cell"]
    flops = ref.train_flops(ctx["conf"], cell["batch"], cell["seq"])
    peak = counts.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops * len(rec["steps"]) / rec["window_s"] \
        / (peak * cell["chips"])
