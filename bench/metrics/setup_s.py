"""Process start to the window's start: imports, weights, SMP spawn and
shared memory, the first three steps and the first published flight, and
every compilation (host clock)."""


def read(rec, ctx):
    return rec.get("setup_s")
