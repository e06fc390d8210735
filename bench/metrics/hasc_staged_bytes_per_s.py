"""Bytes the L2 stager moved into the SMPs' staging rings in the traced
window (the `bytes` of every `repro.hasc.l2.send` span that ends in it),
over the window, in GB/s (`program_trace.reduce`)."""


def read(rec, ctx):
    tr = rec.get("trace") or {}
    if "repro.hasc.l2.send" not in tr.get("program_spans", {}) \
            or not tr.get("window_s"):
        return None
    return tr["staged_bytes"] / tr["window_s"] / 1e9
