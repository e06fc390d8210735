"""Shared arithmetic of the metric readers (not a metric itself)."""
from __future__ import annotations

import statistics


def median_restore(rec, key):
    vals = [r[key] for r in rec["restores"] if r.get(key) is not None]
    return statistics.median(vals) if vals else None
