"""Share of the traced window in which the device is idle while a thread
is inside `repro.hasc.l1.dispatch` (the L1 pump's encode dispatches and
d2h starts): the idle time the saving pipeline's dispatches hold on to
(`program_trace.reduce`)."""


def read(rec, ctx):
    tr = rec.get("trace") or {}
    if "repro.hasc.l1.dispatch" not in tr.get("program_spans", {}) \
            or not tr.get("window_s"):
        return None
    return 100.0 * tr["dispatch_idle_s"] / tr["window_s"]
