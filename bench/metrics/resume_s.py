"""Median over the window's failures of the time from `sess.inject` to the
end of the first step trained on the restored state (host clock, one
process: no restart, no recompilation)."""
from _common import median_restore


def read(rec, ctx):
    return median_restore(rec, "resume_s")
