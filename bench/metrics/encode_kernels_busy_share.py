"""Share of the traced window's device busy time spent in the saving
path's encode kernels (`kernels/stage.py` crc32_chunks and
`kernels/xor_parity.py` xor_reduce): device time the saving takes from
the training stream on this chip."""

KERNELS = ("crc32_chunks", "xor_reduce")       # kept by the trace reduction


def read(rec, ctx):
    tr = rec.get("trace") or {}
    ks = tr.get("kernels") or {}
    if not tr.get("busy_s") or not any(ks.get(k, {}).get("calls")
                                       for k in KERNELS):
        return None
    return 100.0 * sum(ks[k]["seconds"] for k in KERNELS) / tr["busy_s"]
