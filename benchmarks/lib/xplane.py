"""From the profiler's trace (`*.xplane.pb`) to the numbers the per-layer
metrics read.  Everything a PR could be judged by is computed here, from
event names, starts and durations alone.

What a v5e trace looks like (found by hand, PR 22, jax 0.9.0 / libtpu
0.0.34): each chip is a plane `/device:TPU:<n>`.  Its line `XLA Modules`
has one event per execution of a compiled program, named
`jit_<function>(<fingerprint>)`; its line `XLA Ops` has one event per
executed HLO instruction, named by the instruction's whole text
(`%hist_build.16 = f32[8192,125]{...} custom-call(...)`), and a `while` op
encloses the ops of its body, so durations on that line nest and must not
be summed across levels.  A `jax.named_scope` survives only where it names
the instruction itself (the Pallas call under scope `hist_build` is
`%hist_build.<n>`); fusions carry no scope, and device events carry no
`op_name` stat.  Host threads are lines of the plane `/host:CPU`; every
`jax.profiler.TraceAnnotation` (the benchmark's `bench/...` spans, the
program's own under `tpu_telemetry=trace`) is an event on the line of the
thread that opened it, on the same clock as the device events.

The CPU backend has no device plane.  There the executed ops are host
events that carry an `hlo_op` stat, and they stand in for the device so
that a rehearsal walks the same code; no number from such a run is a device
number.
"""

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HOST_PLANE = "/host:CPU"
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"


@dataclass
class Events:
    """Events of one kind on one clock: names, starts and durations in
    seconds."""
    names: list = field(default_factory=list)
    start: np.ndarray = field(default_factory=lambda: np.zeros(0))
    dur: np.ndarray = field(default_factory=lambda: np.zeros(0))

    @classmethod
    def of(cls, rows):
        rows = sorted(rows, key=lambda r: (r[1], -r[2]))
        return cls([r[0] for r in rows],
                   np.array([r[1] for r in rows], np.float64) * 1e-9,
                   np.array([r[2] for r in rows], np.float64) * 1e-9)

    def __len__(self):
        return len(self.names)

    def select(self, keep) -> "Events":
        idx = [i for i, n in enumerate(self.names) if keep(n)]
        return Events([self.names[i] for i in idx], self.start[idx],
                      self.dur[idx])

    def clip(self, t0: float, t1: float) -> "Events":
        """The parts of the events that lie inside [t0, t1]."""
        lo = np.clip(self.start, t0, t1)
        hi = np.clip(self.start + self.dur, t0, t1)
        idx = np.flatnonzero(hi > lo)
        return Events([self.names[i] for i in idx], lo[idx],
                      (hi - lo)[idx])

    def total(self) -> float:
        return float(self.dur.sum())


@dataclass
class Trace:
    ops: dict          # device index -> Events of executed instructions
    modules: dict      # device index -> Events of executed programs
    host: dict         # host line name -> Events of that thread
    on_device: bool    # False when host-run ops stand in for the device


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    if path.endswith(".textproto"):
        with open(path) as f:
            data = ProfileData.from_text_proto(f.read())
    else:
        data = ProfileData.from_file(path)
    return from_profile(data)


def from_profile(data) -> Trace:
    planes = list(data.planes)
    on_device = any(_DEVICE_PLANE.match(p.name) for p in planes)
    ops, modules, host, host_ops = {}, {}, {}, []
    for plane in planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name in (_OPS_LINE, _MODULES_LINE):
                    into = ops if line.name == _OPS_LINE else modules
                    into[int(m.group(1))] = Events.of(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name == _HOST_PLANE:
            for line in plane.lines:
                rows = []
                for e in line.events:
                    row = (e.name, e.start_ns, e.duration_ns)
                    # only a trace without a device plane is searched for
                    # the ops the host ran
                    if not on_device and any(k == "hlo_op"
                                             for k, _ in e.stats):
                        host_ops.append(row)
                    else:
                        rows.append(row)
                if rows:
                    host[line.name] = Events.of(rows)
    if not on_device and host_ops:
        ops = {0: Events.of(host_ops)}
    return Trace(ops, modules, host, on_device)


# ---- interval arithmetic ---------------------------------------------------
def union(start, dur):
    """Disjoint sorted intervals [[lo, hi], ...] covering the same time."""
    order = np.argsort(start, kind="stable")
    out = []
    for lo, hi in zip(np.asarray(start)[order],
                      (np.asarray(start) + np.asarray(dur))[order]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        elif hi > lo:
            out.append([float(lo), float(hi)])
    return out


def length(intervals) -> float:
    return float(sum(hi - lo for lo, hi in intervals))


def gaps(intervals, t0: float, t1: float):
    """What [t0, t1] has left once `intervals` (disjoint, sorted) are taken
    out."""
    out, at = [], t0
    for lo, hi in intervals:
        if lo > at:
            out.append([at, min(lo, t1)])
        at = max(at, hi)
        if at >= t1:
            break
    if at < t1:
        out.append([at, t1])
    return [g for g in out if g[1] > g[0]]


def exposed(events: Events, others: Events) -> float:
    """Seconds of `events` during which nothing of `others` runs: a
    collective's exposed part, with the compute ops as `others`."""
    cover = union(others.start, others.dur)
    mine = union(events.start, events.dur)
    return float(sum(length(gaps(cover, lo, hi)) for lo, hi in mine))


# ---- reductions --------------------------------------------------------------
def window_of(trace: Trace, name: str):
    """(start, end) of the host span `name`, the traced window."""
    for events in trace.host.values():
        hit = events.select(lambda n: n == name)
        if len(hit):
            return float(hit.start[0]), float(hit.start[0] + hit.dur[0])
    raise KeyError(f"the trace has no host span {name!r}")


def busy(trace: Trace, t0: float, t1: float) -> dict:
    """Per device: the disjoint intervals inside [t0, t1] in which an
    operation ran."""
    out = {}
    for dev, events in trace.ops.items():
        inside = events.clip(t0, t1)
        out[dev] = union(inside.start, inside.dur)
    return out


def busy_seconds(trace: Trace, t0: float, t1: float) -> float:
    """Busy time, averaged over the devices that ran anything."""
    per_dev = [length(iv) for iv in busy(trace, t0, t1).values()]
    return float(np.mean(per_dev)) if per_dev else 0.0


def self_times(events: Events) -> np.ndarray:
    """Each event's duration less the part its enclosed events cover (the
    ops of a `while` body lie inside the `while` event)."""
    own = events.dur.copy()
    stack = []  # indices of the events open at this point
    for i in range(len(events)):
        end = events.start[i] + events.dur[i]
        while stack and events.start[i] >= (events.start[stack[-1]]
                                            + events.dur[stack[-1]]):
            stack.pop()
        if stack:
            own[stack[-1]] -= min(end, events.start[stack[-1]]
                                  + events.dur[stack[-1]]) - events.start[i]
        stack.append(i)
    return np.maximum(own, 0.0)


def short_name(name: str) -> str:
    """`%hist_build.16 = f32[...] custom-call(...)` -> `%hist_build.16
    custom-call`; other names as they are, cut to 80 characters."""
    m = re.match(r"^(%[^ ]+) = .*?\)?\s([a-z][a-z0-9-]*)\(", name)
    if m:
        return f"{m.group(1)} {m.group(2)}"
    return name[:80]


def top_device_ops(trace: Trace, t0: float, t1: float, n: int = 10):
    """[[name, seconds], ...]: the instructions with most self time inside
    the window, summed over executions and devices."""
    total = {}
    for events in trace.ops.values():
        inside = events.clip(t0, t1)
        for name, own in zip(inside.names, self_times(inside)):
            key = short_name(name)
            total[key] = total.get(key, 0.0) + float(own)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def host_line_with(trace: Trace, prefix: str):
    """The host thread that opened the spans named `prefix...`: the one
    that drives the device."""
    for events in trace.host.values():
        if any(n.startswith(prefix) for n in events.names):
            return events
    return Events()


def idle_gaps_by_host_span(trace: Trace, t0: float, t1: float,
                           driver_prefix: str = "bench/", n: int = 10):
    """[[host span, seconds], ...]: the device's idle time inside the
    window, each gap charged to the innermost span the driving thread had
    open at the gap's middle (`(none)` where it had none open).  With
    several devices a gap is idle time of the first."""
    per_dev = busy(trace, t0, t1)
    if not per_dev:
        return []
    idle = gaps(per_dev[min(per_dev)], t0, t1)
    spans = host_line_with(trace, driver_prefix)
    ends = spans.start + spans.dur
    total = {}
    for lo, hi in idle:
        mid = (lo + hi) / 2.0
        open_at = np.flatnonzero((spans.start <= mid) & (ends >= mid))
        # sorted by start: the last one open is the innermost
        name = spans.names[open_at[-1]] if len(open_at) else "(none)"
        total[name] = total.get(name, 0.0) + (hi - lo)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]
