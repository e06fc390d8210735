"""Tokens of every step completed in the window over the window's length
(host clock; the window ends with its last step)."""


def read(rec, ctx):
    if not rec["steps"]:
        return None
    return rec["tokens_per_step"] * len(rec["steps"]) / rec["window_s"]
