"""Share of the traced window's device busy time spent in ops under the
train step's `attention` scope, forward and backward
(`program_trace.reduce`)."""


def read(rec, ctx):
    tr = rec.get("trace") or {}
    sec = (tr.get("scope_busy") or {}).get("attention")
    if not sec or not tr.get("busy_s"):
        return None
    return 100.0 * sec / tr["busy_s"]
