"""Median over restores of the loader's span-based RAIM5 decode seconds
(`LoadStats.decode_seconds`)."""
from _common import median_restore


def read(rec, ctx):
    return median_restore(rec, "decode_s")
