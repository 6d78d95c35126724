"""Profiler trace -> device busy time, idle share and breakdown.

``Tracer`` records JAX's profiler trace of the measured window into a
temporary directory, reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData`` and deletes it. ``reduce`` is the arithmetic,
kept apart so that a test can check it on a small recorded trace:

  * busy: per device, the union of the intervals in which an operation
    ran, clipped to the window (the host span ``bench.window``); busy_s is
    its mean over the devices;
  * idle gaps: the complement of that union on the first device, each
    named by the innermost benchmark span the host was in at the gap's
    midpoint;
  * device ops: self seconds per operation name (an op's time less that
    of the ops nested in it on the same line, as a while loop holds its
    body's ops), named by the HLO instruction alone.
"""
from __future__ import annotations

import glob
import re
import shutil
import tempfile

# the benchmark's host spans carry this prefix in the trace: the window's
# own, and those the drivers put around their calls into the program
SPAN_PREFIX = "bench."
WINDOW_SPAN = "window"
OP_LINES = ("XLA Ops", "XLA Modules")  # first one present is used
TOP = 10


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    m = re.match(r"%?([\w.\-]+)", text)
    return m.group(1) if m else text[:64]


def self_times(events):
    """Self time of each (name, start, dur) event of one timeline whose
    events nest: its duration less that of the events directly inside it."""
    out, stack = [], []  # stack of [end, index]
    for i, (name, s, d) in enumerate(sorted(events, key=lambda e: (e[1], -e[2]))):
        while stack and stack[-1][0] <= s:
            stack.pop()
        out.append([name, d])
        if stack:
            out[stack[-1][1]][1] -= d
        stack.append([s + d, i])
    return out


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost(spans, t):
    best = None
    for name, s, d in spans:
        if name != WINDOW_SPAN and s <= t <= s + d:
            if best is None or d < best[1]:
                best = (name, d)
    return best[0] if best else "no span"


def reduce(device_events, host_spans) -> dict:
    """``device_events``: (name, start_ns, dur_ns, device); ``host_spans``:
    (name, start_ns, dur_ns). Returns busy_s, window_s, idle_share and the
    breakdown's two lists."""
    win = [(s, s + d) for n, s, d in host_spans if n == WINDOW_SPAN]
    if win:
        w0, w1 = win[0]
    else:
        w0 = min(s for _, s, _, _ in device_events)
        w1 = max(s + d for _, s, d, _ in device_events)
    per_dev, clipped = {}, {}
    for name, s, d, dev in device_events:
        s0, e0 = max(s, w0), min(s + d, w1)
        if e0 <= s0:
            continue
        per_dev.setdefault(dev, []).append((s0, e0))
        clipped.setdefault(dev, []).append((name, s0, e0 - s0))
    per_op = {}
    for events in clipped.values():
        for name, t in self_times(events):
            per_op[name] = per_op.get(name, 0.0) + t * 1e-9 / len(clipped)
    unions = {dev: _union(iv) for dev, iv in per_dev.items()}
    busy = [sum(e - s for s, e in u) for u in unions.values()]
    busy_s = (sum(busy) / len(busy)) * 1e-9 if busy else 0.0
    window_s = (w1 - w0) * 1e-9
    gaps = []
    if unions:
        first = unions[min(unions)]
        edges = [w0] + [x for iv in first for x in iv] + [w1]
        spans = sorted(zip(edges[::2], edges[1::2]), key=lambda g: g[0] - g[1])
        gaps = [(_innermost(host_spans, (s + e) / 2), (e - s) * 1e-9)
                for s, e in spans[:TOP] if e > s]
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": (1.0 - busy_s / window_s) if window_s > 0 else None,
            "breakdown": {"device_ops": [[n, s] for n, s in ops],
                          "idle_gaps": [[n, s] for n, s in gaps[:TOP]]}}


def read_xplane(path: str):
    """Device events and benchmark host spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_events, host_spans = [], []
    layout = [f"{p.name}: " + ", ".join(f"{ln.name}" for ln in p.lines)
              for p in data.planes]
    dev_planes = [p for p in data.planes if p.name.startswith("/device:")
                  and "CPU" not in p.name]
    for i, plane in enumerate(sorted(dev_planes, key=lambda p: p.name)):
        lines = {ln.name: ln for ln in plane.lines}
        pick = next((lines[n] for n in OP_LINES if n in lines), None)
        for line in ([pick] if pick is not None else plane.lines):
            for ev in line.events:
                device_events.append((op_name(ev.name), ev.start_ns,
                                      ev.duration_ns, i))
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host_spans.append((ev.name[len(SPAN_PREFIX):],
                                           ev.start_ns, ev.duration_ns))
    return device_events, host_spans, layout


class Tracer:
    """Profiler trace of the measured window, reduced on ``stop``."""

    def __init__(self):
        self.dir = None

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)

    def stop(self) -> dict:
        import jax
        jax.profiler.stop_trace()
        try:
            files = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            events, spans, layout = read_xplane(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        out = reduce(events, spans)
        out["device_events"] = len(events)
        out["layout"] = layout
        return out
