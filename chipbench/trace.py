"""Reduction of a JAX profiler trace to device times.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Read with
``jax.profiler.ProfileData`` (nothing else is needed):

* a TPU is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event
  per executed HLO operation and its line ``XLA Modules`` one event per
  executed program (named after the jitted function: ``jit_<name>(<id>)``);
* the host is the plane ``/host:CPU``; ``jax.profiler.TraceAnnotation``
  spans appear there on the Python thread. Host and device timestamps
  share one time base but may disagree by a millisecond or two, which
  matters only at the window's edges and for naming short idle gaps.

The harness marks the measured window with a host span named ``window``;
every device number is clipped to it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[str, float, float]  # (name, start_s, end_s)

WINDOW_SPAN = "window"
_DEVICE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


@dataclass
class Device:
    ops: List[Interval] = field(default_factory=list)
    modules: List[Interval] = field(default_factory=list)


@dataclass
class Trace:
    window: Tuple[float, float]
    devices: Dict[str, Device]
    host: List[Interval]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def module_name(event_name: str) -> str:
    """``jit_train_step(12)`` -> ``train_step``."""
    return _MODULE.match(event_name).group(1)


def load(path: str) -> Trace:
    """Reads the newest ``.xplane.pb`` under ``path`` (a file or a dir)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    pd = ProfileData.from_file(path)
    devices: Dict[str, Device] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            dev = devices.setdefault(plane.name, Device())
            for line in plane.lines:
                dest = {"XLA Ops": dev.ops,
                        "XLA Modules": dev.modules}.get(line.name)
                if dest is None:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    dest.append((e.name, s, s + e.duration_ns * 1e-9))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    host.append((e.name, s, s + e.duration_ns * 1e-9))
    wins = [h for h in host if h[0] == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace {path} has no host span {WINDOW_SPAN!r}")
    w = max(wins, key=lambda h: h[2] - h[1])
    return Trace((w[1], w[2]), devices, host)


def _clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in iv if e > lo and s < hi]


def union(iv: List[Interval]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for _, s, e in sorted(iv, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(tr: Trace, device: Optional[str] = None) -> float:
    """Seconds of the window in which some operation ran, averaged over the
    devices (or for one)."""
    names = [device] if device else sorted(tr.devices)
    if not names:
        return 0.0
    tot = 0.0
    for d in names:
        ops = _clip(tr.devices[d].ops, *tr.window)
        tot += sum(e - s for s, e in union(ops))
    return tot / len(names)


def idle_share(tr: Trace) -> Optional[float]:
    """Percent of the window in which no operation ran on the device."""
    if not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s(tr) / tr.window_s)


def module_s(tr: Trace, names, exclude: bool = False) -> Optional[float]:
    """Device seconds of the programs whose ``module_name`` is in ``names``
    (or, with ``exclude``, is not), summed in the window and averaged over
    devices. None when no such program ran."""
    names = set(names)
    tot, seen = 0.0, False
    for dev in tr.devices.values():
        for n, s, e in _clip(dev.modules, *tr.window):
            if (module_name(n) in names) != exclude:
                tot += e - s
                seen = True
    return tot / max(len(tr.devices), 1) if seen else None


def module_counts(tr: Trace) -> Dict[str, int]:
    """Executions per program in the window, on the first device."""
    out: Dict[str, int] = {}
    if not tr.devices:
        return out
    dev = tr.devices[sorted(tr.devices)[0]]
    for n, s, e in _clip(dev.modules, *tr.window):
        out[module_name(n)] = out.get(module_name(n), 0) + 1
    return out


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The device operations that took most time in the window (summed by
    name over executions, first device)."""
    if not tr.devices:
        return []
    tot: Dict[str, float] = {}
    dev = tr.devices[sorted(tr.devices)[0]]
    for name, s, e in _clip(dev.ops, *tr.window):
        name = name.split(" = ")[0]  # the HLO instruction, not its text
        tot[name] = tot.get(name, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, spans, n: int = 10) -> List[List]:
    """Idle seconds of the first device, summed by what the host was doing:
    the harness span (of ``spans``) that covers the middle of each gap, or
    ``window`` where the host was between spans."""
    if not tr.devices:
        return []
    lo, hi = tr.window
    dev = tr.devices[sorted(tr.devices)[0]]
    busy = union(_clip(dev.ops, lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    # the harness's spans inside the window run one after another; a gap
    # that none of them covers is the host's time between them
    inner = sorted((h for h in tr.host
                    if h[0] in set(spans) and h[0] != WINDOW_SPAN),
                   key=lambda h: h[1])
    starts = [h[1] for h in inner]
    tot: Dict[str, float] = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        j = bisect.bisect_right(starts, mid) - 1
        name = inner[j][0] if j >= 0 and inner[j][2] > mid else WINDOW_SPAN
        tot[name] = tot.get(name, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
