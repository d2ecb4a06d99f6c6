"""Reduction of a JAX profiler trace to device time, per program and per
operation, and to the device's idle gaps by what the host was doing.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData`` and nothing else. Device planes are named
``/device:<KIND>:<n>``; their ``XLA Ops`` line holds one event per operation
executed and their ``XLA Modules`` line one per program executed. The
traced window is the host span ``WINDOW_SPAN`` the harness opens around it.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

WINDOW_SPAN = "bench.trace_window"
CONTAINERS = {"while", "conditional", "call"}
_KIND = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
_SHAPE = re.compile(r"=\s*\(?([a-z0-9]+\[[0-9,]*\])")


def op_kind(op: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event named by its instruction text
    (``%convert.3 = bf16[8,128]{1,0} convert(...)`` -> ``convert``)."""
    _, _, rhs = op.partition(" = ")
    m = _KIND.search(" " + rhs)
    return m.group(1) if m else ""


def short_op(op: str) -> str:
    name = op.split(" = ", 1)[0]
    shape = _SHAPE.search(op)
    return " ".join(x for x in (name, op_kind(op),
                                shape.group(1) if shape else "") if x)
_DEVICE_PREFIX = "/device:"


@dataclasses.dataclass
class DeviceTimeline:
    name: str
    ops: List[Tuple[str, str, int, int]]      # (op, module, start, end) ns
    modules: List[Tuple[str, int, int]]       # (module, start, end) ns


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]                   # ns, host time base
    devices: List[DeviceTimeline]
    host: List[Tuple[str, int, int]]          # (span name, start, end) ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        return float(np.mean([_union_ns(_op_intervals(d)) * 1e-9
                              for d in self.devices]))

    def module_times(self, pattern: str) -> List[float]:
        """Device seconds of each execution of programs whose name holds
        ``pattern``, over all devices."""
        return [(e - s) * 1e-9 for d in self.devices
                for name, s, e in d.modules if pattern in name]

    def kernel_times(self, program: str) -> List[float]:
        """Device seconds of each Pallas kernel call (a custom call) inside
        programs whose name holds ``program``."""
        return [(e - s) * 1e-9 for d in self.devices
                for op, mod, s, e in d.ops
                if program in mod and op_kind(op) == "custom-call"]

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` operations that took the most device time, summed
        over executions, named ``<program>: <op> <kind> <shape>``. Loop
        and call operations, which hold other operations, are left out."""
        acc: Dict[Tuple[str, str], float] = collections.Counter()
        for d in self.devices:
            for op, mod, s, e in d.ops:
                acc[(mod, op)] += (e - s) * 1e-9
        out = []
        for (mod, op), secs in acc.most_common():
            if op_kind(op) in CONTAINERS:
                continue
            out.append([f"{mod}: {short_op(op)}" if mod else short_op(op),
                        secs])
            if len(out) == n:
                break
        return out

    def idle_gaps(self, n: int = 10, longest: int = 4000) -> List[list]:
        """Idle device seconds grouped by the innermost host span that
        covers most of each gap (the ``longest`` gaps of each device)."""
        if not self.host:
            hs = he = np.zeros(0)
            names: List[str] = []
        else:
            names = [h[0] for h in self.host]
            hs = np.asarray([h[1] for h in self.host], np.float64)
            he = np.asarray([h[2] for h in self.host], np.float64)
        acc: Dict[str, float] = collections.Counter()
        for d in self.devices:
            gaps = _gaps(_op_intervals(d), self.window)
            gaps.sort(key=lambda g: g[0] - g[1])
            for g0, g1 in gaps[:longest]:
                label = "no host span"
                if len(hs):
                    ov = np.minimum(he, g1) - np.maximum(hs, g0)
                    # innermost: the shortest span among those that cover
                    # at least half of the gap, else the largest overlap
                    cover = np.flatnonzero(ov >= 0.5 * (g1 - g0))
                    if len(cover):
                        label = names[cover[np.argmin(he[cover] - hs[cover])]]
                    elif ov.max() > 0:
                        label = names[int(np.argmax(ov))]
                acc[label] += (g1 - g0) * 1e-9
        return [[k, v / max(len(self.devices), 1)]
                for k, v in acc.most_common(n)]


def _op_intervals(d: DeviceTimeline):
    if d.ops:
        return [(s, e) for _, _, s, e in d.ops]
    return [(s, e) for _, s, e in d.modules]


def _union_ns(iv) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(iv):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(iv, window) -> List[Tuple[int, int]]:
    out, t = [], window[0]
    for s, e in sorted(iv):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def _clip(s, e, window):
    s, e = max(s, window[0]), min(e, window[1])
    return (s, e) if e > s else None


def _in_programs(ops, mods):
    """Each op with the program whose execution holds its start (a device
    runs one program at a time)."""
    if not ops:
        return []
    mods = sorted(mods, key=lambda m: m[1])
    starts = np.asarray([m[1] for m in mods] or [0], np.int64)
    ends = np.asarray([m[2] for m in mods] or [0], np.int64)
    op_s = np.asarray([o[1] for o in ops], np.int64)
    idx = np.searchsorted(starts, op_s, side="right") - 1
    held = (idx >= 0) & (op_s < ends[np.maximum(idx, 0)]) & bool(mods)
    return [(name, mods[i][0] if h else "", s, e)
            for (name, s, e), i, h in zip(ops, idx.tolist(), held.tolist())]


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    return reduce_planes(pd.planes)


def reduce_planes(planes) -> Trace:
    """Build a ``Trace`` from xplane planes (``ProfileData.planes`` or
    objects with the same ``name``/``lines``/``events`` shape)."""
    planes = list(planes)
    window = None
    host: List[Tuple[str, int, int]] = []
    for p in planes:
        if p.name.startswith(_DEVICE_PREFIX):
            continue
        for line in p.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if ev.name == WINDOW_SPAN:
                    window = (s, e)
                elif e > s:
                    host.append((ev.name, s, e))
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    host = [(n, s, e) for n, s, e in host if _clip(s, e, window)]
    devices = []
    for p in planes:
        if not p.name.startswith(_DEVICE_PREFIX):
            continue
        raw_ops, mods, names = [], [], {}
        for line in p.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    c = _clip(int(ev.start_ns),
                              int(ev.start_ns) + int(ev.duration_ns), window)
                    if c:
                        name = ev.name
                        raw_ops.append((names.setdefault(name, name), *c))
            elif line.name == "XLA Modules":
                for ev in line.events:
                    c = _clip(int(ev.start_ns),
                              int(ev.start_ns) + int(ev.duration_ns), window)
                    if c:
                        mods.append((ev.name.split("(")[0], *c))
        if raw_ops or mods:
            devices.append(DeviceTimeline(p.name, _in_programs(raw_ops, mods),
                                          mods))
    return Trace(window, devices, host)
