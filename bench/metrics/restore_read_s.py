"""Median over restores of the loader's span-based read seconds
(`LoadStats.read_seconds`)."""
from _common import median_restore


def read(rec, ctx):
    return median_restore(rec, "read_s")
