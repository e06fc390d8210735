"""Share of the traced window's device busy time spent in the grouped
expert products (megablox `gmm`, and `tgmm` for the weight gradients,
which the name matches too): device time of the MoE layer's matmuls.
Their operations are not counted, so this is no roofline: the rows they
take are those routed to the held experts, which move with the router
from step to step."""

KERNELS = ("gmm",)                   # kept by the trace reduction (tgmm too)


def read(rec, ctx):
    tr = rec.get("trace") or {}
    k = (tr.get("kernels") or {}).get("gmm") or {}
    if not tr.get("busy_s") or not k.get("calls"):
        return None
    return 100.0 * k["seconds"] / tr["busy_s"]
