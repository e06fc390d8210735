"""The program's own spans and scopes in a profiler trace.

`trace_reduce` names what the benchmark sees from outside the program;
this adds what the program records about itself, on the same clock:

  host spans  every `repro.<layer>.<what>` span (`repro.core.spans`),
              with its metadata (`bytes` of `repro.hasc.l2.send`) and the
              line (thread) it ran on;
  scopes      each device op's top-level named scope of the train step
              (`attention`, `mlp`, `embed`, `head_loss`, `optimizer`),
              read from the op_name the op's trace event carries.

`reduce` returns `trace_reduce.reduce`'s numbers with these added:

  program_spans     {span: [seconds inside the window, count]}
  staged_bytes      bytes of the `repro.hasc.l2.send` spans that end in
                    the window
  dispatch_idle_s   device idle time in the window while any thread is
                    inside `repro.hasc.l1.dispatch` (averaged over devices)
  scope_busy        {scope: device seconds}: the union of the intervals of
                    each scope's ops, averaged over devices (a time two
                    scopes cover, a loop op and its body, counts in both)
  gap_spans         for each listed idle gap, {span: count} of the program
                    spans open on other threads than the window's at its
                    midpoint; the gap's name gains `|<most common span>`

On a trace without program spans and scopes the result is
`trace_reduce.reduce`'s, key for key.
"""
from __future__ import annotations

import collections
import re

from trace_reduce import (DEVICE_PLANE, OP_LINE, WINDOW_SPAN, _union,
                          reduce as reduce_base)

PREFIX = "repro."
DISPATCH = "repro.hasc.l1.dispatch"
SEND = "repro.hasc.l2.send"
SCOPES = ("embed", "attention", "mlp", "head_loss", "optimizer")
OP_NAME_STATS = ("tf_op", "op_name")     # where an op event carries it
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(SCOPES) + r")(?=[/)]|$)")


def scope_of(op_name: str):
    """The outermost program scope in an op_name path, or None."""
    m = _SCOPE.search(op_name or "")
    return m.group(1) if m else None


def load(path: str) -> dict:
    """The program's events in an `.xplane.pb`, as plain data:
    {"program": [(span, start_ns, dur_ns, line, {stat: value})],
     "main_line": the line holding `bench.window` (None without one),
     "scopes": {plane: [(scope, start_ns, dur_ns)]}}.
    A line is the index of a thread's line in the host plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    prog, main, scopes = [], None, collections.defaultdict(list)
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for ev in line.events:
                    stats = {k: v for k, v in ev.stats}
                    op = next((stats[k] for k in OP_NAME_STATS
                               if k in stats), None)
                    sc = scope_of(str(op)) if op is not None else None
                    if sc is not None:
                        scopes[plane.name].append((sc, ev.start_ns,
                                                   ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for li, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        prog.append((ev.name, ev.start_ns, ev.duration_ns,
                                     li, {k: v for k, v in ev.stats}))
                    elif ev.name == WINDOW_SPAN:
                        main = li
    return {"program": prog, "main_line": main, "scopes": dict(scopes)}


def reduce(events: dict, kernels=(), top: int = 10) -> dict:
    """`trace_reduce.reduce` of `events` (its keys and a `load`'s), with
    the program's numbers added (module docstring)."""
    out = reduce_base(events, kernels, top)
    prog = events.get("program") or []
    scopes = events.get("scopes") or {}
    if not out.get("devices") or not (prog or scopes):
        return out
    w0, w1 = _window(events)
    devs = events["device"]
    nd = len(devs)
    spans = collections.defaultdict(lambda: [0.0, 0])
    staged = 0
    for name, s, d, _, meta in prog:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            spans[name][0] += (b - a) / 1e9
            spans[name][1] += 1
        if name == SEND and w0 <= s + d <= w1:
            staged += int(meta.get("bytes", 0))
    dispatch = _union(
        (max(s, w0), min(s + d, w1)) for n, s, d, _, _ in prog
        if n == DISPATCH and s + d > w0 and s < w1)
    idle_dispatch, gaps = 0, []
    for plane, evs in devs.items():
        idle = _idle(evs, w0, w1)
        idle_dispatch += _overlap(idle, dispatch)
        gaps += [(b - a, a, b) for a, b in idle]
    gaps.sort(reverse=True)
    busy = collections.Counter()
    for plane, evs in scopes.items():
        by = collections.defaultdict(list)
        for sc, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                by[sc].append((a, b))
        for sc, ivs in by.items():
            busy[sc] += sum(b - a for a, b in _union(ivs))
    main = events.get("main_line")
    others = [(s, s + d, n) for n, s, d, li, _ in prog if li != main]
    gap_spans = []
    for row, (_, a, b) in zip(out["gaps"], gaps):   # the same order
        t = (a + b) / 2
        c = collections.Counter(n for s, e, n in others if s <= t < e)
        if c:                                # most common, then by name
            row[0] += "|" + min(c, key=lambda n: (-c[n], n))
        gap_spans.append(dict(c))
    out.update(program_spans=dict(spans), staged_bytes=staged,
               dispatch_idle_s=idle_dispatch / nd / 1e9,
               scope_busy={k: v / nd / 1e9 for k, v in busy.items()},
               gap_spans=gap_spans)
    return out


def _window(events):
    """The window as `trace_reduce.reduce` takes it."""
    win = [(s, s + d) for n, s, d in events["host"]
           if n == WINDOW_SPAN]
    if win:
        return win[0]
    devs = events["device"].values()
    return (min(s for evs in devs for _, s, _, _ in evs),
            max(s + d for evs in devs for _, s, d, _ in evs))


def _idle(evs, w0, w1):
    """The idle intervals of one device inside the window."""
    merged = _union(
        (max(s, w0), min(s + d, w1)) for _, s, d, _ in evs
        if min(s + d, w1) > max(s, w0))
    out, t = [], w0
    for a, b in merged + [[w1, w1]]:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    return out


def _overlap(xs, ys):
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = total = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total
