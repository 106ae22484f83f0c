"""Device busy time, idle share and the breakdown from a profiler trace.

The traced run wraps the profiled seconds in a host annotation
(``WINDOW_EVENT``); the window is that annotation's span.  Busy time is
the union of the op intervals on each device plane inside the window,
averaged over the devices; the idle share is one minus busy over the
window.  The top list sums device time by program (the ``XLA Modules``
line, else by op).  Each idle gap of the first device is labelled by the
benchmark's own host annotation (``bench.*``) that overlaps it most, or
``no bench span``; ``idle_gaps`` sums the gaps' seconds by label.

``reduce`` takes planes as ``jax.profiler.ProfileData`` gives them:
objects with ``name`` and ``lines``; lines with ``name`` and ``events``;
events with ``name``, ``start_ns`` and ``duration_ns``.  A trace with no
window annotation or no device plane gives ``None``: nothing to read.
"""

from __future__ import annotations

import glob
import os

import numpy as np

WINDOW_EVENT = "bench.profile_window"
DEVICE_PREFIX = "/device:"
#: busy time is read from the ops; the top list names the programs
OP_LINES = ("XLA Ops", "XLA Modules")
PROGRAM_LINES = ("XLA Modules", "XLA Ops")
NO_SPAN = "no bench span"
TOP = 10


def load(trace_dir: str) -> list:
    """The planes of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return []
    return list(ProfileData.from_file(max(paths, key=os.path.getmtime))
                .planes)


def _union(iv: list) -> list:
    out: list = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(plane, names: tuple) -> list:
    """(start, end, short name) of the first line of ``names`` that has
    events; an HLO op's name is cut at its `` = `` (the trace gives the
    whole instruction)."""
    lines = {ln.name: ln for ln in plane.lines}
    for name in names:
        if name in lines:
            out = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                    ev.name.split(" = ")[0]) for ev in lines[name].events]
            if out:
                return out
    return []


def reduce(planes) -> dict | None:
    win = None
    bench: list = []
    devices: list = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = _events(plane, OP_LINES)
            if ops:
                devices.append((ops, _events(plane, PROGRAM_LINES)))
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name == WINDOW_EVENT:
                    win = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith("bench.") and ev.duration_ns > 0:
                    bench.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    if win is None or not devices:
        return None
    w0, w1 = win
    busy_ns = 0.0
    by_op: dict = {}
    gaps: list = []
    for k, (ops, progs) in enumerate(devices):
        for s, e, n in progs:
            if e > w0 and s < w1:
                by_op[n] = by_op.get(n, 0.0) + (min(e, w1) - max(s, w0))
        merged = _union([(max(s, w0), min(e, w1)) for s, e, _ in ops
                         if e > w0 and s < w1])
        busy_ns += sum(e - s for s, e in merged)
        if k == 0:
            edge = w0
            for s, e in merged:
                if s > edge:
                    gaps.append((edge, s))
                edge = max(edge, e)
            if edge < w1:
                gaps.append((edge, w1))
    n_dev = len(devices)
    busy_s = busy_ns / n_dev / 1e9
    window_s = (w1 - w0) / 1e9
    labels: dict = {}
    bs = np.asarray([b[0] for b in bench], np.float64)
    be = np.asarray([b[1] for b in bench], np.float64)
    for gs, ge in gaps:
        label = NO_SPAN
        if len(bs):
            ov = np.minimum(be, ge) - np.maximum(bs, gs)
            i = int(np.argmax(ov))
            if ov[i] > 0:
                label = bench[i][2]
        labels[label] = labels.get(label, 0.0) + (ge - gs) / 1e9
    ops_top = sorted(((n, ns / n_dev / 1e9) for n, ns in by_op.items()),
                     key=lambda kv: -kv[1])[:TOP]
    gaps_top = sorted(labels.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "device_ops": [[n, s] for n, s in ops_top],
            "idle_gaps": [[n, s] for n, s in gaps_top]}
