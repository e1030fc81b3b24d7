"""From the profiler's ``.xplane.pb`` to the per-layer numbers.

Two steps, so that the arithmetic can be checked on a small recorded
trace: ``load_events`` reads the file with jax alone into plain tuples,
``reduce_events`` turns them into the summary the per-layer readers use:

  window_s        first host span's start to the last one's end
  busy_s          union of the device-op intervals inside the window,
                  averaged over the device planes
  device_ops      [(name, seconds)] summed by op name, largest first
  idle_gaps       [(innermost host event open while the device idled,
                  seconds)], largest first
  spans           number of host spans (collects) in the window
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# the line of a device plane that holds one event per executed HLO op;
# the others (modules, steps, trace-me) cover the same time again
OPS_LINE = "XLA Ops"
TOP = 10
# gaps shorter than this are the device's own turn-around between two ops
# of one program; they are summed under one name, not looked up
SHORT_GAP_NS = 20_000.0
SHORT_GAP = "(under 20us between ops)"
NO_SPAN = "(no span open)"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(xplane_path: str, span: str) -> dict:
    """{"device": {plane: [(name, start_ns, dur_ns)]},
        "host": [(name, start_ns, dur_ns)]}: the op events of every
    device plane, and every event of the host thread that holds the
    spans named ``span``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    device, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            device[plane.name] = [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ln in ops for ev in ln.events]
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                evs = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                       for ev in ln.events]
                if any(name == span for name, _, _ in evs):
                    host += evs
    return {"device": device, "host": host}


def _union(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _short(name: str) -> str:
    """A trace name as a key: no space, comma or quote, at most 64 long."""
    return re.sub(r"[^A-Za-z0-9_.:\-]", "_", name)[:64]


def reduce_events(events: dict, span: str) -> dict | None:
    """The summary, or None where the trace holds no span or no device op
    (nothing to read)."""
    spans = sorted((s, s + d) for name, s, d in events["host"]
                   if name == span)
    if not spans or not any(events["device"].values()):
        return None
    w0, w1 = spans[0][0], spans[-1][1]
    host = [(s, s + d, name) for name, s, d in events["host"]
            if d > 0 and s < w1 and s + d > w0]
    h0 = np.array([h[0] for h in host])
    h1 = np.array([h[1] for h in host])
    busy_ns = 0.0
    op_ns: dict = {}
    gap_ns: dict = {}
    for plane_events in events["device"].values():
        inside = []
        for name, s, d in plane_events:
            s1, e1 = max(s, w0), min(s + d, w1)
            if e1 > s1:
                inside.append((s1, e1))
                op_ns[name] = op_ns.get(name, 0.0) + (e1 - s1)
        merged = _union(inside)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            if g1 - g0 < SHORT_GAP_NS:
                gap_ns[SHORT_GAP] = gap_ns.get(SHORT_GAP, 0.0) + (g1 - g0)
                continue
            # split the gap among the host events open during it, each
            # stretch going to the innermost (shortest) one open in it
            over = np.nonzero((h0 < g1) & (h1 > g0))[0]
            cuts = sorted({g0, g1, *(t for i in over for t in (h0[i], h1[i])
                                     if g0 < t < g1)})
            for c0, c1 in zip(cuts, cuts[1:]):
                open_ = [i for i in over if h0[i] <= c0 and h1[i] >= c1]
                who = (host[min(open_, key=lambda i: h1[i] - h0[i])][2]
                       if open_ else NO_SPAN)
                gap_ns[who] = gap_ns.get(who, 0.0) + (c1 - c0)
    n = len(events["device"])

    def top(d):
        return [[_short(k), float(v) / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"window_s": (w1 - w0) / 1e9, "busy_s": busy_ns / n / 1e9,
            "device_ops": top(op_ns), "idle_gaps": top(gap_ns),
            "spans": len(spans)}


def summarize(trace_dir: str, span: str) -> dict | None:
    return reduce_events(load_events(find_xplane(trace_dir), span), span)
