#!/usr/bin/env python3
"""The serving loop's host spans in a profiler trace, and what they say
about the device's idle time.

  python3 bench/spans.py <trace.xplane.pb> [--replica <n>=<name> ...]

reads a trace kept by ``bench/run.py --trace 1 --keep-trace <dir>`` and
prints one JSON object: the traced window's idle seconds by label, its
longest idle gaps with their labels, each program's device time and calls,
and each replica's loop phases (summed and self seconds in the window).
``--replica 0=replica0`` names the replica on ``/device:TPU:0``; by default
device ``n`` holds ``replica<n>``, as ``bench/harness.py`` builds a fleet.

The program writes a host span ``dds.<replica>.<phase>`` around each phase
of a replica's serving loop (``repro.serving.engine.LOOP_SPANS``) on the
profiler's clock.  An idle gap of a device is labelled
``<replica>:<phase>`` after the deepest span of that device's replica
that covers more than half of the gap (else the span that covers most of
it); a gap that no span of its replica touches reads ``unattributed``.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace_reduce  # noqa: E402

SPAN_PREFIX = "dds."
UNATTRIBUTED = "unattributed"
STEP_PROGRAM = re.compile(r"_step(_paged)?_impl")

Gap = Tuple[str, float, float]          # (device plane, start_ns, end_ns)


@dataclass
class Spans:
    """One replica's loop spans: phase names, their ends (ns, the trace's
    clock), and how deep each nests on its thread (0 outermost)."""

    phase: List[str] = field(default_factory=list)
    start: List[float] = field(default_factory=list)
    end: List[float] = field(default_factory=list)
    depth: List[int] = field(default_factory=list)


def device_number(plane: str) -> int:
    """``n`` of a device plane ``/device:TPU:<n>``."""
    return int(plane.rsplit(":", 1)[1])


def read_spans(pd) -> Dict[str, Spans]:
    """The program's host spans ``dds.<replica>.<phase>`` in a profile
    (``jax.profiler.ProfileData``), per replica.  Spans with no replica in
    the name (the router's ``dds.route`` and ``dds.admission``) are left
    out."""
    out: Dict[str, Spans] = {}
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            evs = []
            for ev in line.events:
                name = ev.name.split("#", 1)[0]     # "#k=v#": span arguments
                if name.startswith(SPAN_PREFIX):
                    parts = name.split(".", 2)
                    if len(parts) == 3:
                        evs.append((ev.start_ns, ev.end_ns, parts[1], parts[2]))
            evs.sort(key=lambda e: (e[0], -e[1]))
            open_ends: List[float] = []             # spans enclosing this one
            for s, e, replica, phase in evs:
                while open_ends and open_ends[-1] <= s:
                    open_ends.pop()
                sp = out.setdefault(replica, Spans())
                sp.phase.append(phase)
                sp.start.append(s)
                sp.end.append(e)
                sp.depth.append(len(open_ends))
                open_ends.append(e)
    return out


def bounds(devices: Dict[str, trace_reduce.DeviceEvents],
           window: Optional[trace_reduce.Event]) -> Tuple[float, float]:
    """The traced window's ends (ns), as ``trace_reduce.reduce`` takes
    them: the window span, else the span of the device events."""
    if window is not None:
        return window[1], window[2]
    evs = [ev for d in devices.values() for ev in d.ops + d.modules]
    return min(s for _, s, _ in evs), max(e for _, _, e in evs)


def device_gaps(devices: Dict[str, trace_reduce.DeviceEvents],
                window: Optional[trace_reduce.Event]) -> List[Gap]:
    """Every idle interval of every device inside the window, longest
    first: the gaps of ``trace_reduce.reduce``, with their device and
    their ends on the trace's clock."""
    lo, hi = bounds(devices, window)
    gaps: List[Gap] = []
    for plane, d in devices.items():
        if not (d.ops or d.modules):
            continue
        busy = trace_reduce._union([(max(s, lo), min(e, hi))
                                    for _, s, e in d.ops or d.modules
                                    if min(e, hi) > max(s, lo)])
        prev = lo
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                gaps.append((plane, prev, s))
            prev = max(prev, e)
    gaps.sort(key=lambda g: g[1] - g[2])
    return gaps


def gap_labels(gaps: List[Gap], spans: Dict[str, Spans],
               replica_of: Dict[int, str]) -> List[str]:
    """A label for each gap (same order): ``<replica>:<phase>`` of the
    span that best covers it on the replica of its device (see the module
    docstring), or ``unattributed``.  ``replica_of`` maps a device's
    number (``/device:TPU:<n>``) to the replica on it."""
    arrays = {r: (np.asarray(sp.start), np.asarray(sp.end),
                  np.asarray(sp.depth)) for r, sp in spans.items()}
    labels = []
    for plane, gs, ge in gaps:
        replica = replica_of.get(device_number(plane))
        if replica not in arrays:
            labels.append(UNATTRIBUTED)
            continue
        start, end, depth = arrays[replica]
        cover = np.minimum(end, ge) - np.maximum(start, gs)
        if not (cover > 0).any():
            labels.append(UNATTRIBUTED)
            continue
        most = cover > (ge - gs) / 2
        i = int(np.argmax(np.where(most, depth, -1)) if most.any()
                else np.argmax(cover))
        labels.append(f"{replica}:{spans[replica].phase[i]}")
    return labels


def idle_by_label(gaps: List[Gap], labels: List[str],
                  devices: int) -> Dict[str, float]:
    """Idle seconds per label, averaged over the devices like
    ``Reduced.busy_s`` (they sum to ``window_s - busy_s``), most first."""
    out: Dict[str, float] = {}
    for (_, s, e), label in zip(gaps, labels):
        out[label] = out.get(label, 0.0) + (e - s) / 1e9 / devices
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def phase_seconds(sp: Spans, lo: float, hi: float) -> Dict[str, Dict[str, float]]:
    """Per phase, the seconds its spans cover inside ``[lo, hi)`` (ns)
    and their self seconds (less what the spans nested in them cover)."""
    evs = [(ph, min(max(s, lo), hi), min(max(e, lo), hi))
           for ph, s, e in zip(sp.phase, sp.start, sp.end)]
    out: Dict[str, Dict[str, float]] = {}
    for (ph, s, e), own in zip(evs, trace_reduce._self_times(evs)):
        row = out.setdefault(ph, {"s": 0.0, "self_s": 0.0, "count": 0})
        if e > s:
            row["s"] += (e - s) / 1e9
            row["self_s"] += own / 1e9
            row["count"] += 1
    return out


def sync_edges(sp: Spans, steps: List[trace_reduce.Event]) -> Dict[str, float]:
    """Each ``decode.sync`` span against the decode step program on its
    replica's device that overlaps it most: the median wait from the span's
    start to the program's start (``launch_ms``) and from the program's
    end to the span's end (``tail_ms``).  The program cannot end after the
    host has its result, so a negative ``tail_ms`` would mean the host and
    device clocks of the trace disagree."""
    st = np.asarray([s for _, s, _ in steps])
    en = np.asarray([e for _, _, e in steps])
    launch, tail = [], []
    for ph, s, e in zip(sp.phase, sp.start, sp.end):
        if ph != "decode.sync" or not len(st):
            continue
        overlap = np.minimum(en, e) - np.maximum(st, s)
        i = int(np.argmax(overlap))
        if overlap[i] > 0:
            launch.append(st[i] - s)
            tail.append(e - en[i])
    if not launch:
        return {}
    return {"launch_ms": float(np.median(launch)) / 1e6,
            "tail_ms": float(np.median(tail)) / 1e6,
            "tail_min_ms": float(np.min(tail)) / 1e6, "n": len(launch)}


def summarize(pd, replica_of: Dict[int, str], top: int = 10) -> Dict:
    """What ``main`` prints, from a loaded profile."""
    devices, window = trace_reduce.from_profile(pd)
    red = trace_reduce.reduce(devices, window)
    gaps = device_gaps(devices, window)
    spans = read_spans(pd)
    labels = gap_labels(gaps, spans, replica_of)
    lo, hi = bounds(devices, window)
    programs = {name: {"s": secs, "calls": red.module_calls[name],
                       "ms_per_call": 1e3 * secs / red.module_calls[name]}
                for name, secs in sorted(red.module_s.items(),
                                         key=lambda kv: -kv[1])}
    plane_of = {replica_of[device_number(p)]: p for p in devices
                if device_number(p) in replica_of}
    return {"window_s": red.window_s, "busy_s": red.busy_s,
            "idle_by_label": idle_by_label(gaps, labels, red.devices),
            "idle_gaps": [[label, (e - s) / 1e9]
                          for (_, s, e), label in zip(gaps[:top], labels)],
            "programs": programs,
            "phases": {r: phase_seconds(sp, lo, hi)
                       for r, sp in spans.items()},
            "sync_edges": {r: sync_edges(sp, [
                ev for ev in devices[plane_of[r]].modules
                if STEP_PROGRAM.search(ev[0])])
                for r, sp in spans.items() if r in plane_of}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("xplane")
    ap.add_argument("--replica", action="append", default=[],
                    help="<device number>=<replica name>")
    args = ap.parse_args()
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(args.xplane)
    replica_of = {int(k): v for k, v in
                  (r.split("=", 1) for r in args.replica)}
    if not replica_of:
        devices, _ = trace_reduce.from_profile(pd)
        replica_of = {device_number(p): f"replica{device_number(p)}"
                      for p in devices}
    print(json.dumps(summarize(pd, replica_of)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
