"""Pallas TPU kernels for the perf-critical hot spots:

* xor_parity    — RAIM5 parity encode/decode (the paper's EC hot loop,
                  moved on-accelerator as a beyond-paper option)
* stage         — fused snapshot-bucket encode (XOR parity fold + CRC32
                  before the d2h copy; the REFT-Sn device encode path)
* ssd_scan      — Mamba2 chunked state-space-duality scan
* swa_attention — banded (sliding-window) flash attention

Each kernel ships <name>.py (pl.pallas_call + BlockSpec), a pure-jnp
oracle in ref.py, swept in tests/, and — except `stage`, whose entry point
is `stage.encode_bucket` — a jit'd wrapper in ops.py.
"""
from repro.kernels.ops import (
    ssd_scan, swa_attention, xor_parity_decode, xor_parity_encode,
)
from repro.kernels.stage import bucket_crc, encode_bucket

__all__ = ["bucket_crc", "encode_bucket", "ssd_scan", "swa_attention",
           "xor_parity_decode", "xor_parity_encode"]
