"""Program spans on the profiler's clock.

`span(name, counter, key, **meta)` opens a `jax.profiler.TraceAnnotation`
and, given a counter dict and key, adds the span's `perf_counter`
duration there: a level's counter (`PipelineResult`) and its spans in a
trace are one measurement under one name.  Keyword metadata become the
event's stats in a trace (`bytes=nb` reads back as the stat
`('bytes', nb)`).  A TraceAnnotation is recorded on the clock of the
device planes, so a trace puts host spans and device ops on one time base.

Every span is named `repro.<layer>.<what>`.  Only the trainer process
opens spans: the spawned SMP imports no JAX and never this module.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

from jax.profiler import TraceAnnotation

__all__ = ["span"]


class span:
    """Context manager: a trace span, plus its seconds under `key` of
    `counter` when one is given (also when the body raises)."""

    __slots__ = ("_ann", "_counter", "_key", "_t0")

    def __init__(self, name: str, counter: Optional[Dict[str, float]] = None,
                 key: Optional[str] = None, **meta):
        self._ann = TraceAnnotation(name, **meta)
        self._counter, self._key = counter, key

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._counter is not None:
            self._counter[self._key] += time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
