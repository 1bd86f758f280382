"""From a profiler trace to device busy time, idle share and its causes.

The trace is the `.xplane.pb` that `jax.profiler` writes.  The device
planes are named `/device:TPU:<i>`; their `XLA Ops` line holds one event
per operation that ran on that chip.  The benchmark puts its own host
spans on the same clock with `jax.profiler.TraceAnnotation`: `window`
around the measured loop, and inside it `call`, `wait` and `next_source`
(a closed loop) or the sender's `submit` and `sleep` (an open loop).

- busy: the union of the chip's op intervals inside the window;
- idle share: 1 - busy / window, averaged over the chips;
- device ops: seconds per op inside the window, averaged over chips, by
  the HLO instruction name the trace gives it (`sketch_gram.1`,
  `fusion.248`: a Pallas kernel is named after its kernel function);
- idle gaps: the longest stretches inside the window in which no chip ran
  anything, each named by the host span that covers the most of it.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"
HOST_SPANS = ("call", "wait", "next_source", "submit", "sleep")
TOP = 10


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def gaps(busy, lo, hi):
    """The stretches of [lo, hi] that the merged `busy` intervals leave."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(a, b):
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def name_gap(gap, spans):
    """The host span name that covers the most of `gap`, or "host" when no
    benchmark span covers it."""
    best, best_t = "host", 0.0
    for name, s, e in spans:
        t = overlap(gap, (s, e))
        if t > best_t:
            best, best_t = name, t
    return best


def reduce_events(devices: dict, spans: list) -> dict | None:
    """`devices`: {chip name: [(op name, start_s, end_s), ...]};
    `spans`: [(span name, start_s, end_s), ...] on the same clock, one of
    them named WINDOW_SPAN.  None where there is no window or no chip."""
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    window_s = hi - lo
    host = [(n, s, e) for n, s, e in spans if n in HOST_SPANS]
    busy_s, op_s, every = [], defaultdict(float), []
    for events in devices.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if e > lo and s < hi]
        busy = merge([(s, e) for _, s, e in inside])
        busy_s.append(sum(e - s for s, e in busy))
        for n, s, e in inside:
            op_s[n] += (e - s) / len(devices)
        every += busy
    idle = gaps(merge(every), lo, hi)
    idle.sort(key=lambda g: g[1] - g[0], reverse=True)
    busy_mean = sum(busy_s) / len(busy_s)
    return {
        "window_s": window_s,
        "busy_s": busy_mean,
        "busy_s_per_chip": busy_s,
        "idle_share": 1.0 - busy_mean / window_s,
        "device_ops": sorted(([n, t] for n, t in op_s.items()),
                             key=lambda x: x[1], reverse=True)[:TOP],
        "idle_gaps": [[name_gap(g, host), g[1] - g[0]] for g in idle[:TOP]],
    }


def op_name(event_name: str) -> str:
    """`%fusion.248 = f32[...] fusion(...)` -> `fusion.248`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read_xplane(path) -> tuple[dict, list]:
    """(devices, spans) as `reduce_events` takes them, in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, spans = {}, []
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            events = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    events += [(op_name(ev.name), ev.start_ns * 1e-9,
                                ev.end_ns * 1e-9) for ev in line.events]
            if events:
                devices[plane.name] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                          for ev in line.events if ev.name in wanted]
    return devices, spans


def find_xplane(log_dir) -> Path | None:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def reduce_trace(path) -> dict | None:
    return reduce_events(*read_xplane(path))
