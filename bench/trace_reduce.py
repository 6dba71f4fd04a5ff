"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

The benchmark marks the traced window with a host span named
``WINDOW_SPAN``; the profiler puts host spans and device events on one
clock.  For each device plane (``/device:TPU:<n>``) the reduction takes
its operations (the ``XLA Ops`` line) and its programs (``XLA Modules``),
clips them to the window, and gives:

* ``busy_s`` — the union of the intervals in which an operation ran,
  averaged over the devices; ``window_s`` — the window's length;
* ``op_s`` — summed device seconds per operation name (averaged over the
  devices), and ``module_s`` / ``module_calls`` per program name;
* ``self_s`` — the same per name, less the time of the operations nested
  in it (a ``while`` loop's body runs inside the loop's own event);
* ``gaps`` — every idle interval inside the window, longest first.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench_traced_window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = Tuple[str, float, float]            # (name, start_ns, end_ns)


@dataclass
class DeviceEvents:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    devices: int
    op_s: Dict[str, float]
    module_s: Dict[str, float]
    module_calls: Dict[str, float]
    gaps: List[Tuple[float, float]]          # (start offset s, length s)
    self_s: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_of(self, pattern: str) -> float:
        """Device seconds of every operation whose name matches."""
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_s.items() if rx.search(n))

    def module_seconds_calls(self, pattern: str) -> Tuple[float, float]:
        rx = re.compile(pattern)
        secs = sum(s for n, s in self.module_s.items() if rx.search(n))
        calls = sum(c for n, c in self.module_calls.items() if rx.search(n))
        return secs, calls


def read(path: str) -> Tuple[Dict[str, DeviceEvents], Optional[Event]]:
    """Device events per plane, and the window span if the trace has one."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Tuple[Dict[str, DeviceEvents], Optional[Event]]:
    devices: Dict[str, DeviceEvents] = {}
    window: Optional[Event] = None
    for plane in pd.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        if is_dev:
            devices[plane.name] = DeviceEvents()
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if not is_dev:
                    if name == WINDOW_SPAN:
                        window = (name, ev.start_ns, ev.end_ns)
                    continue
                item = (op_name(name), ev.start_ns, ev.end_ns)
                if line.name == OPS_LINE:
                    devices[plane.name].ops.append(item)
                elif line.name == MODULES_LINE:
                    devices[plane.name].modules.append(item)
    return devices, window


def op_name(raw: str) -> str:
    """The instruction's name: a TPU trace names an operation by its whole
    HLO text (``%fusion.12 = bf16[...] fusion(...)``)."""
    m = re.match(r"%?([^\s=]+)\s*=", raw)
    return m.group(1) if m else raw


def op_family(name: str) -> str:
    """An operation's name without its instance number (``fusion.12`` ->
    ``fusion``), so that top lists group like operations."""
    return re.sub(r"[.:]\d+$", "", name)


def _self_times(events: List[Event]) -> List[float]:
    """Each event's duration less that of the events nested directly in it
    (events of one line nest or follow one another)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e - s for _, s, e in events]
    stack: List[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce(devices: Dict[str, DeviceEvents],
           window: Optional[Event] = None) -> Reduced:
    """Reduce device events to the traced window (the whole span of the
    device events when the trace carries no window span)."""
    devs = {k: v for k, v in devices.items() if v.ops or v.modules}
    if not devs:
        raise ValueError("the trace holds no device events")
    if window is None:
        lo = min(s for d in devs.values() for _, s, _ in d.ops + d.modules)
        hi = max(e for d in devs.values() for _, _, e in d.ops + d.modules)
    else:
        lo, hi = window[1], window[2]
    if hi <= lo:
        raise ValueError("empty traced window")
    n = len(devs)
    busy = 0.0
    op_s: Dict[str, float] = {}
    module_s: Dict[str, float] = {}
    module_calls: Dict[str, float] = {}
    self_s: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for d in devs.values():
        spans = []
        evs = [(name, max(s, lo), min(e, hi)) for name, s, e in d.ops or d.modules]
        evs = [ev for ev in evs if ev[2] > ev[1]]
        own = _self_times(evs)
        for (name, s, e), o in zip(evs, own):
            spans.append((s, e))
            if d.ops:
                op_s[name] = op_s.get(name, 0.0) + (e - s) / 1e9 / n
                self_s[name] = self_s.get(name, 0.0) + o / 1e9 / n
        for name, s, e in d.modules:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            module_s[name] = module_s.get(name, 0.0) + (e - s) / 1e9 / n
            module_calls[name] = module_calls.get(name, 0.0) + 1.0 / n
        merged = _union(spans)
        busy += sum(e - s for s, e in merged) / 1e9 / n
        prev = lo
        for s, e in merged + [(hi, hi)]:
            if s > prev:
                gaps.append(((prev - lo) / 1e9, (s - prev) / 1e9))
            prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    return Reduced((hi - lo) / 1e9, busy, n, op_s, module_s, module_calls,
                   gaps, self_s)


def breakdown(red: Reduced, top: int = 10) -> Dict[str, List]:
    """The result line's ``breakdown``: the device operations (grouped by
    family, by their own time without what nests in them) that took most
    time, and the longest idle gaps.  The program has no host spans yet,
    so no gap can be attributed."""
    fam: Dict[str, float] = {}
    for name, s in (red.self_s or red.op_s).items():
        fam[op_family(name)] = fam.get(op_family(name), 0.0) + s
    ops = sorted(fam.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [["unattributed", g[1]] for g in red.gaps[:top]]}
