"""Reduce a profiler trace to the device numbers the benchmark reports.

The reduction works on plain event lists, so tests check it on synthetic
events; ``load_profile`` turns the profiler's ``.xplane.pb`` into them.

- busy time: the union of the intervals in which an operation ran on a
  device, averaged over the devices used;
- idle share: 1 - busy / window;
- collective time: the summed durations of the collective operations
  (all-gather, collective-permute, all-reduce, all-to-all,
  reduce-scatter), averaged over the devices, by the XLA op kind in the
  op's name;
- the breakdown: the device operations that took most time (each op's
  own time, without the ops nested in it), and the idle time of the first
  device by the host events that cover it.
"""

from __future__ import annotations

import collections
import glob
import heapq
import os
from dataclasses import dataclass

#: XLA op kinds that move data between chips.
COLLECTIVE_KINDS = ("all-gather", "collective-permute", "all-reduce",
                    "all-to-all", "reduce-scatter")
#: Label of idle time in which no host event ran.
NO_HOST_EVENT = "(no host event)"


@dataclass(frozen=True)
class Event:
    name: str
    start: float   # seconds
    end: float     # seconds


def merged(events) -> list[tuple[float, float]]:
    """Disjoint, sorted intervals covering the events."""
    out: list[list[float]] = []
    for s, e in sorted((ev.start, ev.end) for ev in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(events) -> float:
    return sum(e - s for s, e in merged(events))


def idle_gaps(events) -> list[tuple[float, float]]:
    """The gaps between a device's busy intervals."""
    spans = merged(events)
    return [(a[1], b[0]) for a, b in zip(spans, spans[1:]) if b[0] > a[1]]


def op_name(name: str) -> str:
    """The instruction's own name (``%fusion.40``) out of the HLO text
    that a TPU trace gives as an op's name."""
    return name.split(" = ", 1)[0].strip()


def is_collective(name: str) -> bool:
    return any(kind in op_name(name) for kind in COLLECTIVE_KINDS)


def self_times(events) -> list[tuple[str, float]]:
    """Each op's own time: its duration less that of the ops nested in it
    (a loop's body ops run inside the loop's own event)."""
    order = sorted(events, key=lambda ev: (ev.start, -ev.end))
    out: list[list] = []
    stack: list[int] = []
    for ev in order:
        while stack and out[stack[-1]][2] <= ev.start:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= ev.end - ev.start
        out.append([op_name(ev.name), ev.end - ev.start, ev.end])
        stack.append(len(out) - 1)
    return [(name, t) for name, t, _ in out]


def gap_attribution(gaps, host) -> collections.Counter:
    """Idle seconds by what the host was doing: for each gap, the part that
    each host event's name covers, and the part no host event covers
    (``NO_HOST_EVENT``; host Python work is not in the trace). Names that
    nest or run on several threads at once each count their own cover.
    One sweep over the host events sorted by start, keeping those that
    overlap the current gap."""
    events = sorted(host, key=lambda ev: ev.start)
    active: list[tuple[float, int]] = []   # heap of (end, index)
    out: collections.Counter = collections.Counter()
    k = 0
    for s, e in gaps:
        while k < len(events) and events[k].start < e:
            heapq.heappush(active, (events[k].end, k))
            k += 1
        while active and active[0][0] <= s:
            heapq.heappop(active)
        by_name: dict[str, list] = collections.defaultdict(list)
        for _end, i in active:
            ev = events[i]
            a, b = max(s, ev.start), min(e, ev.end)
            if b > a:
                by_name[ev.name].append(Event(ev.name, a, b))
        everything = []
        for name, evs in by_name.items():
            out[name] += busy_seconds(evs)
            everything += evs
        out[NO_HOST_EVENT] += (e - s) - busy_seconds(everything)
    return out


def reduce_trace(devices: dict[str, list[Event]], host: list[Event],
                 window_s: float, top: int = 10) -> dict:
    """The numbers of one traced window. ``devices`` maps each device used
    to its operations; ``host`` holds the host threads' events."""
    names = sorted(devices)
    n = max(1, len(names))
    busy = [busy_seconds(devices[d]) for d in names]
    op_time: collections.Counter = collections.Counter()
    collective = 0.0
    for d in names:
        for name, t in self_times(devices[d]):
            op_time[name] += t / n
            if is_collective(name):
                collective += t / n
    gap_time: collections.Counter = collections.Counter()
    if names:
        gap_time = gap_attribution(idle_gaps(devices[names[0]]), host)
    busy_s = sum(busy) / n
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": (1.0 - busy_s / window_s) if window_s > 0 else None,
        "collective_s": collective,
        "device_ops": [[k, v] for k, v in op_time.most_common(top)],
        "idle_gaps": [[k, v] for k, v in gap_time.most_common(top)],
    }


def _events_of(line) -> list[Event]:
    return [Event(ev.name, ev.start_ns * 1e-9,
                  (ev.start_ns + ev.duration_ns) * 1e-9)
            for ev in line.events]


#: The device line that holds one event per XLA operation.
OPS_LINE = "XLA Ops"


def load_profile(log_dir: str, n_devices: int
                 ) -> tuple[dict, list[Event], list[str]]:
    """(device events, host events, plane summary) from the newest
    ``.xplane.pb`` under ``log_dir``. Devices are the first ``n_devices``
    TPU planes; their ops come from the ``XLA Ops`` line. The summary names
    each plane and line with its event count and first event names: the
    look at a trace that this reduction was written against."""
    import jax

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return {}, [], []
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    summary: list[str] = []
    for plane in data.planes:
        for line in plane.lines:
            events = _events_of(line)
            names = sorted({ev.name for ev in events[:200]})
            summary.append(f"{plane.name} | {line.name} | {len(events)} | "
                           + ", ".join(names)[:300])
            if plane.name.startswith("/device:TPU:"):
                if line.name == OPS_LINE:
                    devices[plane.name] = events
            elif plane.name.startswith("/host:CPU"):
                host.extend(events)
    keep = sorted(devices, key=lambda d: int(d.rsplit(":", 1)[1]))
    return {d: devices[d] for d in keep[:n_devices]}, host, summary
