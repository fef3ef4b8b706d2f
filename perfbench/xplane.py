"""Reduce a JAX profiler trace (`*.xplane.pb`) to what the per-layer metrics
read: per device the program (module) runs, the operations on the two op
lines as intervals, and the host's named spans, all in nanoseconds on the
trace's clock.

Interval arithmetic is plain functions on lists of ``(start, end)`` pairs,
tested on made-up intervals and on the recorded v5e trace under
``perf/onchip_r04/trace`` (perfbench/tests/test_xplane.py).

What the lines of a TPU device plane hold (looked at by hand in that trace):
``XLA Modules`` one event per program run; ``XLA Ops`` the operations the
core runs, one after another — a ``*-done`` here is the core waiting for an
asynchronous operation; ``Async XLA Ops`` the spans of asynchronous
operations (copies, slices, collectives) from start to done, overlapping
the first line. An event's name is the HLO instruction text,
``%name = type op(operands), attributes``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import pathlib
import re

SYNC_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULE_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = re.compile(r"^/host:CPU")

_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
_KIND = re.compile(r"kind=(k\w+)")
_SUFFIX = re.compile(r"[.\d]+$")


# -- interval arithmetic -----------------------------------------------------

def union(intervals) -> list:
    """Merged, sorted, non-overlapping intervals covering the same points."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    """Total length covered (overlaps counted once)."""
    return float(sum(e - s for s, e in union(intervals)))


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list:
    """The part of ``a`` that no interval of ``b`` covers."""
    out, b = [], union(b)
    ends = [be for _, be in b]
    for s, e in union(a):
        cur = s
        for j in range(bisect.bisect_right(ends, s), len(b)):
            bs, be = b[j]
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(intervals, lo, hi) -> list:
    """The parts of ``[lo, hi]`` that ``intervals`` leave uncovered."""
    return subtract([(lo, hi)], intervals)


# -- the trace ---------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Op:
    name: str      # the instruction's own name, e.g. "fusion.12"
    text: str      # the whole HLO text of the event
    start: float
    end: float

    @property
    def is_collective(self) -> bool:
        return bool(_COLLECTIVE.search(self.name))

    @property
    def category(self) -> str:
        """The name without its number, and a fusion's kind: the only
        attribution a program without named scopes allows."""
        base = _SUFFIX.sub("", self.name)
        kind = _KIND.search(self.text)
        return f"{base}:{kind.group(1)}" if kind else base


@dataclasses.dataclass(frozen=True)
class Device:
    index: int
    modules: tuple   # Op per program run
    ops: tuple       # SYNC_LINE
    async_ops: tuple  # ASYNC_LINE

    @functools.cached_property
    def window(self) -> tuple:
        """From the first program's start to the last program's end."""
        if not self.modules:
            raise ValueError(f"device {self.index}: no program in the trace")
        return (min(m.start for m in self.modules),
                max(m.end for m in self.modules))

    @functools.cached_property
    def busy(self) -> list:
        """Where any operation ran, inside the window."""
        lo, hi = self.window
        return union(clip(((o.start, o.end)
                           for o in self.ops + self.async_ops), lo, hi))

    def exposed_collectives(self) -> list:
        """Where a collective ran (on either line) and the core ran nothing
        else: collective intervals less every other operation of the
        synchronous line."""
        lo, hi = self.window
        coll = [(o.start, o.end) for o in self.ops + self.async_ops
                if o.is_collective]
        other = [(o.start, o.end) for o in self.ops if not o.is_collective]
        return clip(subtract(coll, other), lo, hi)


@dataclasses.dataclass(frozen=True)
class Trace:
    devices: tuple        # Device, by index
    host_spans: tuple     # Op: the host's named spans (TraceAnnotation)

    def busy_and_window_s(self) -> tuple:
        """(busy seconds, window seconds), each averaged over the devices."""
        busy = [length(d.busy) for d in self.devices]
        win = [d.window[1] - d.window[0] for d in self.devices]
        n = len(self.devices)
        return sum(busy) / n * 1e-9, sum(win) / n * 1e-9


def _ops(line) -> tuple:
    out = []
    for e in line.events:
        text = e.name
        name = text.split(" = ", 1)[0].lstrip("%")
        out.append(Op(name, text, float(e.start_ns),
                      float(e.start_ns + e.duration_ns)))
    return tuple(out)


def find_xplane(trace_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one *.xplane.pb under {trace_dir}, found {found}")
    return found[0]


def load(path, host_span_names=()) -> Trace:
    """Read one xplane file. ``host_span_names``: the names of host events
    to keep (the harness's own annotations); every other host event (the
    Python tracer's frames, the runtime's own) is dropped."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host = [], []
    keep = set(host_span_names)
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            if SYNC_LINE not in lines:
                continue
            devices.append(Device(
                index=int(m.group(1)),
                modules=_ops(lines[MODULE_LINE]) if MODULE_LINE in lines
                else (),
                ops=_ops(lines[SYNC_LINE]),
                async_ops=_ops(lines[ASYNC_LINE]) if ASYNC_LINE in lines
                else ()))
        elif keep and HOST_PLANE.match(plane.name):
            for ln in plane.lines:
                host += [o for o in _ops(ln) if o.name in keep]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane with an op line")
    devices.sort(key=lambda d: d.index)
    return Trace(tuple(devices), tuple(sorted(host, key=lambda o: o.start)))


# -- the breakdown -----------------------------------------------------------

def top_device_ops(device: Device, n: int = 10) -> list:
    """[[category, seconds]] of the synchronous line inside the window,
    largest first."""
    lo, hi = device.window
    total = collections.Counter()
    for o in device.ops:
        s, e = max(o.start, lo), min(o.end, hi)
        if e > s:
            total[o.category] += e - s
    return [[k, v * 1e-9] for k, v in total.most_common(n)]


def idle_gaps_by_host(device: Device, host_spans, n: int = 10) -> list:
    """[[where/what-the-host-did, seconds]]: every uncovered stretch of the
    device's window, named by whether it lies inside a program run or
    between two, and by the host span open at its middle ("-" if none)."""
    lo, hi = device.window
    runs = [(m.start, m.end) for m in device.modules]
    total = collections.Counter()
    for s, e in gaps(device.busy, lo, hi):
        mid = (s + e) / 2
        where = ("in-program" if any(a <= mid < b for a, b in runs)
                 else "between-programs")
        doing = next((h.name for h in host_spans
                      if h.start <= mid < h.end), "-")
        total[f"{where}/{doing}"] += e - s
    return [[k, v * 1e-9] for k, v in total.most_common(n)]
