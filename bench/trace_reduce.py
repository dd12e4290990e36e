"""Reduce a JAX profiler trace to the device-time numbers of the benchmark.

Input: the ``*.xplane.pb`` that ``jax.profiler`` writes, read by
``bench/xplane.py`` (each event with its metadata's statistics, which
``jax.profiler.ProfileData`` leaves out):

- device planes (``/device:TPU:<i>``): the line of XLA operations; each
  event is one HLO op with its duration, and its ``tf_op`` (or ``name``)
  statistic carries the op's scope path (the ``jax.named_scope`` names of
  the program, e.g. ``.../krylov/precond/precond/vcycle/mg/level0/...``);
- host planes: the harness's ``TraceAnnotation`` spans (``bench/unit``
  around each unit of work, ``bench/wait`` around its
  ``block_until_ready``) and the runtime's own host events.

Output (``Reduced``): the traced window (first ``bench/unit`` start to the
last end), the union of device-busy intervals in it, device time per scope
pattern (each op's own time, without the ops nested in it), the idle gaps attributed to the innermost host
span open in each, and the ``breakdown`` lists of the result line.  Every
number is averaged over the devices used.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

UNIT_SPAN = "bench/unit"
OPS_LINES = ("XLA Ops",)


class Op:
    __slots__ = ("start", "end", "name", "scope", "own")

    def __init__(self, start, end, name, scope):
        self.start, self.end = start, end
        self.name, self.scope = name, scope
        self.own = end - start


def own_times(ops: List[Op]) -> None:
    """Set each op's ``own`` time: its duration less that of the ops
    nested directly in it.  A loop's event on the line of XLA operations
    spans the events of its body's operations, so durations alone would
    count the body twice."""
    stack: List[Op] = []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        o.own = o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].own -= o.end - o.start
        stack.append(o)


def _scope_of(stats: dict) -> str:
    for key in ("tf_op", "long_name", "name"):
        v = stats.get(key)
        if isinstance(v, str) and "/" in v:
            return v.split(" = ")[0] if key == "long_name" else v
    return ""


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Merge intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def matches(scope: str, pattern: str) -> bool:
    """``pattern`` (e.g. ``hgemv/`` or ``precond/vcycle``) is a run of whole
    path segments of ``scope``."""
    return ("/" + pattern.strip("/") + "/") in ("/" + scope + "/")


class Reduced:
    """Device time of one traced window (see module docstring)."""

    def __init__(self, device_ops: List[List[Op]],
                 host_spans: List[Tuple[float, float, str]],
                 window: Tuple[float, float]):
        self.t0, self.t1 = window
        self.devices = [[o for o in ops if o.end > self.t0 and
                         o.start < self.t1] for ops in device_ops]
        for ops in self.devices:
            own_times(ops)
        self.host = host_spans
        self.busy = [union(clip([(o.start, o.end) for o in ops],
                                self.t0, self.t1))
                     for ops in self.devices]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        return sum(sum(e - s for s, e in b) for b in self.busy) * 1e-9 / \
            len(self.busy)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def _mean(self, pick) -> float:
        return sum(sum(o.own for o in ops if pick(o))
                   for ops in self.devices) * 1e-9 / len(self.devices)

    def scope_s(self, *patterns: str) -> float:
        """Device seconds of ops whose scope holds any of ``patterns``."""
        return self._mean(lambda o: any(matches(o.scope, p)
                                        for p in patterns))

    def has_scope(self, pattern: str) -> bool:
        return any(matches(o.scope, pattern) for ops in self.devices
                   for o in ops)

    def gaps(self, device: int = 0) -> List[Tuple[float, float]]:
        out, t = [], self.t0
        for s, e in self.busy[device]:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.t1:
            out.append((t, self.t1))
        return out

    def _host_names(self, times: List[float]) -> List[str]:
        """Innermost (shortest) host span open at each of ``times``
        (sorted): one sweep over the spans sorted by start."""
        spans = sorted(self.host)
        out, active, k = [], [], 0
        for t in times:
            while k < len(spans) and spans[k][0] <= t:
                active.append(spans[k])
                k += 1
            active = [sp for sp in active if sp[1] >= t]
            inner = min(active, key=lambda sp: sp[1] - sp[0], default=None)
            out.append(inner[2] if inner else "no host span")
        return out

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops: Dict[str, float] = defaultdict(float)
        for o in self.devices[0]:
            ops[o.scope or o.name] += o.own * 1e-9
        idle: Dict[str, float] = defaultdict(float)
        gaps = self.gaps(0)
        names = self._host_names([0.5 * (s + e) for s, e in gaps])
        for (s, e), name in zip(gaps, names):
            idle[name] += (e - s) * 1e-9
        rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
        return {"device_ops": [[k, v] for k, v in rank(ops)],
                "idle_gaps": [[k, v] for k, v in rank(idle)]}


def reduce_profile(pd, n_devices: int = 1) -> Reduced:
    """Device planes as above.  Only a trace with no device plane at all,
    one of the CPU backend (the harness's rehearsal), lets its XLA ops,
    host events that carry an ``hlo_op`` statistic, stand in for one
    device; a device plane without XLA ops is an error."""
    device_ops: List[List[Op]] = []
    cpu_ops: List[Op] = []
    host: List[Tuple[float, float, str]] = []
    device_planes = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            device_planes += 1
            if len(device_ops) >= n_devices:
                continue
            ops: List[Op] = []
            for line in plane.lines:
                if line.name not in OPS_LINES:
                    continue
                for ev in line.events:
                    st = dict(ev.stats)
                    ops.append(Op(ev.start_ns, ev.end_ns, ev.name,
                                  _scope_of(st)))
            if not ops:
                raise ValueError(f"device plane {plane.name!r} holds no "
                                 f"line named one of {OPS_LINES}")
            device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    st = dict(ev.stats)
                    if "hlo_op" in st:
                        cpu_ops.append(Op(ev.start_ns, ev.end_ns, ev.name,
                                          _scope_of(st)))
                    else:
                        host.append((ev.start_ns, ev.end_ns, ev.name))
    if not device_planes and cpu_ops:
        device_ops = [cpu_ops]
    if not device_ops:
        raise ValueError("trace holds no device operations")
    units = [(s, e) for s, e, n in host if n == UNIT_SPAN]
    if units:
        window = (min(s for s, _ in units), max(e for _, e in units))
    else:
        window = (min(o.start for ops in device_ops for o in ops),
                  max(o.end for ops in device_ops for o in ops))
    return Reduced(device_ops, host, window)


def find_xplane(directory) -> Optional[str]:
    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def reduce_file(path, n_devices: int = 1) -> Reduced:
    from bench.xplane import Profile
    return reduce_profile(Profile.from_file(path), n_devices)


def reduce_dir(directory, n_devices: int = 1) -> Reduced:
    path = find_xplane(directory)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce_file(path, n_devices)
