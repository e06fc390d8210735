"""From a profiler trace to the device numbers a run reports.

A trace is read into plain event tuples (`load`), and everything after
that is arithmetic on tuples (`reduce`), so the reduction can be checked
on a small recorded trace without a chip:

  busy      the union of the intervals in which an operation ran on a
            device, inside the window, averaged over the devices;
  programs  device time by compiled program (top entries: the train
            step, the saving path's gather and encode programs, ...);
  kernels   device time, calls and bytes of each named kernel;
  gaps      the idle intervals of the window, each named by the
            benchmark's innermost host span (`bench.*`) that covers it.

The window is the `bench.window` host span when the trace has one.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINE = "XLA Ops"                  # every operation, nested ones too
MODULE_LINE = "XLA Modules"          # one event per program execution
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# a kernel's op text: "%crc32_chunks.1 = u32[8,128]{..} custom-call(
# u32[8192,128]{..} %reshape.2), ..." -- the first operand's shape
OPERAND = re.compile(r"custom-call\(u32\[([0-9,]+)\]")


def load(path: str, kernels=()) -> dict:
    """Events of an `.xplane.pb` as plain data:
    {"device": {plane: [(op, start_ns, dur_ns, shape or None)]},
     "modules": {plane: [(program, start_ns, dur_ns)]},
     "host": [(span, start_ns, dur_ns)]}.
    An op is named by its HLO name (`%fusion.12`); for ops of the named
    `kernels` the shape of their first uint32 operand is kept, so the
    kernel's bytes can be counted.  A program is named without its hash."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev, mods = collections.defaultdict(list), collections.defaultdict(list)
    host = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OP_LINE:
                    for ev in line.events:
                        text = ev.name
                        shape = None
                        if any(k in text for k in kernels):
                            m = OPERAND.search(text)
                            if m:
                                shape = [int(x) for x in
                                         m.group(1).split(",")]
                        dev[plane.name].append(
                            (text.split(" ", 1)[0], ev.start_ns,
                             ev.duration_ns, shape))
                elif line.name == MODULE_LINE:
                    for ev in line.events:
                        mods[plane.name].append(
                            (ev.name.split("(", 1)[0], ev.start_ns,
                             ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append((ev.name, ev.start_ns, ev.duration_ns))
    return {"device": dict(dev), "modules": dict(mods), "host": host}


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(events: dict, kernels=(), top: int = 10) -> dict:
    """The numbers a traced run reports (seconds, except counts)."""
    host = events["host"]
    win = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    devs = events["device"]
    if not devs:
        return {"devices": 0}
    if win:
        w0, w1 = win[0]
    else:
        w0 = min(s for evs in devs.values() for _, s, _, _ in evs)
        w1 = max(s + d for evs in devs.values() for _, s, d, _ in evs)
    spans = sorted(((s, s + d, n) for n, s, d in host
                    if n != WINDOW_SPAN), key=lambda x: x[0])
    busy_ns, progs = 0, collections.Counter()
    for plane, evs in events.get("modules", {}).items():
        for name, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                progs[name] += b - a
    kern = {k: {"seconds": 0.0, "calls": 0, "shapes": []} for k in kernels}
    gaps = []
    for plane, evs in devs.items():
        ivs = []
        for name, s, d, shape in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            for k in kernels:
                if k in name:
                    kern[k]["seconds"] += (b - a) / 1e9
                    kern[k]["calls"] += 1
                    kern[k]["shapes"].append(shape)
        merged = _union(ivs)
        busy_ns += sum(b - a for a, b in merged)
        t = w0
        for a, b in merged + [[w1, w1]]:
            if a > t:
                gaps.append((a - t, t, a))
            t = max(t, b)
    nd = len(devs)
    for k in kern:
        kern[k]["seconds"] /= nd
    gaps.sort(reverse=True)
    return {
        "devices": nd,
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / nd / 1e9,
        "programs": [[n, v / nd / 1e9] for n, v in progs.most_common(top)],
        "kernels": kern,
        "gaps": [[_who(spans, (a + b) / 2), g / 1e9]
                 for g, a, b in gaps[:top]],
    }


def _who(spans, t):
    """The innermost benchmark span covering time t, or 'none'."""
    best = None
    for a, b, n in spans:
        if a > t:
            break
        if a <= t < b and (best is None or a >= best[0]):
            best = (a, b, n)
    return best[2] if best else "none"


def read_saved(path: str) -> dict:
    """Events kept as gzipped JSON (the recorded test trace)."""
    with gzip.open(path, "rt") as f:
        return json.load(f)
