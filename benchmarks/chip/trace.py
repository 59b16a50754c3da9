"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

* busy time: the union of the intervals in which an operation ran on a
  device (the "XLA Ops" line of each ``/device:`` plane), inside the
  measured window, averaged over the devices;
* operation time by name, and program time by jitted program (the "XLA
  Modules" line), each event's duration clipped to the window. A TPU names
  an op by its HLO text (``%name = shape op(operands)``): the name is the
  part before " = ", and the operand shapes stay in ``Event.name``;
* idle time by host span: every stretch of the window in which no device
  op ran is charged to the benchmark's host span that covers it, the
  earlier names of ``span_order`` first, and to ``no_host_span`` where none
  does.

The window is the host span named ``window_span``. Device and host events
share the profiler's clock. Only JAX is needed to read the file
(``jax.profiler.ProfileData``).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

import numpy as np

SPAN_PREFIX = "chipbench."


def device_lines(plane: str, line: str) -> str | None:
    """Which device events a line holds: ``ops``, ``modules`` or None."""
    if not plane.startswith("/device:"):
        return None
    return {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line)


@dataclasses.dataclass
class Event:
    name: str
    start_ns: int
    end_ns: int

    @property
    def short(self) -> str:
        """An op's name without its HLO text; a program's without its id."""
        return self.name.split(" = ", 1)[0].split("(", 1)[0]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over devices
    n_devices: int
    op_seconds: dict                    # op name -> seconds (all devices)
    op_counts: dict                     # op name -> events
    module_seconds: dict                # jitted program -> seconds
    idle_by_span: dict                  # host span name -> idle seconds
    ops: list                           # the window's device Events

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def top_ops(self, n: int = 10) -> list:
        return [[k, v] for k, v in sorted(self.op_seconds.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> list:
        return [[k, v] for k, v in sorted(self.idle_by_span.items(),
                                          key=lambda kv: -kv[1])[:n]]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, lines=device_lines, host_prefix: str = SPAN_PREFIX):
    """([{"ops": [...], "modules": [...]} per device], host span events).

    ``lines(plane_name, line_name)`` says which lines hold device ops and
    programs (:func:`device_lines` by default); every other event whose name
    starts with ``host_prefix`` is a host span."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        dev = {"ops": [], "modules": []}
        for line in plane.lines:
            kind = lines(plane.name, line.name)
            for ev in line.events:
                e = Event(ev.name, int(ev.start_ns),
                          int(ev.start_ns + ev.duration_ns))
                if kind is not None:
                    dev[kind].append(e)
                elif ev.name.startswith(host_prefix):
                    spans.append(e)
        if dev["ops"]:
            devices.append(dev)
    return devices, spans


def merge(intervals) -> list[tuple[int, int]]:
    """Sorted, disjoint union of (start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list[tuple[int, int]]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def reduce(devices: list[dict], spans: list[Event],
           window_span: str, span_order=()) -> TraceSummary:
    """The window's busy time, op and program time by name and idle time
    by span. ``devices`` as :func:`load` gives them."""
    win = [s for s in spans if s.name == window_span]
    if not win:
        raise ValueError(f"no {window_span!r} span in the trace")
    lo, hi = win[-1].start_ns, win[-1].end_ns
    op_ns = collections.Counter()
    op_n = collections.Counter()
    mod_ns = collections.Counter()
    busy = []
    idle_by = collections.Counter()
    in_window = []
    names = [n for n in span_order] + sorted(
        {s.name for s in spans if s.name != window_span} - set(span_order))
    for dev in devices:
        iv = []
        for ev in dev["ops"]:
            c = clip([(ev.start_ns, ev.end_ns)], lo, hi)
            if not c:
                continue
            in_window.append(ev)
            op_ns[ev.short] += length(c)
            op_n[ev.short] += 1
            iv.extend(c)
        for ev in dev.get("modules", ()):
            mod_ns[ev.short] += length(clip([(ev.start_ns, ev.end_ns)],
                                            lo, hi))
        on = merge(iv)
        busy.append(length(on))
        idle = subtract([(lo, hi)], on)
        for name in names:
            cover = merge(clip([(s.start_ns, s.end_ns) for s in spans
                                if s.name == name], lo, hi))
            rest = subtract(idle, cover)
            idle_by[name] += (length(idle) - length(rest)) / len(devices)
            idle = rest
        idle_by["no_host_span"] += length(idle) / len(devices)
    return TraceSummary(
        window_s=(hi - lo) / 1e9,
        busy_s=float(np.mean(busy)) / 1e9 if busy else 0.0,
        n_devices=len(devices),
        op_seconds={k: v / 1e9 for k, v in op_ns.items()},
        op_counts=dict(op_n),
        module_seconds={k: v / 1e9 for k, v in mod_ns.items() if v > 0},
        idle_by_span={k: v / 1e9 for k, v in idle_by.items() if v > 0},
        ops=in_window)
