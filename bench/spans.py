"""The program's own spans in a profiler trace, and the device's idle
time put down to them.

The program opens host spans named ``vfl.<layer>.<what>`` (`repro.obs`)
on the profiler's clock.  `program_spans` reads them from an
``.xplane.pb``; `attribute` and `gaps` work on plain events, so they are
tested on synthetic ones.

Definitions, over a traced window [t0, t1):

* innermost span at an instant: of the program spans open then, on any
  host thread, the one that started last;
* idle time of a device is booked to the layer of the innermost span:
  ``protocol``     a ``vfl.conductor.*`` span other than the step, a
                   ``vfl.protocol.*`` or a ``vfl.transport.*`` span;
  ``crypto_host``  a ``vfl.crypto.*`` span (noise draws, limb
                   conversions, the backend calls' own host code);
  ``gc``           a ``vfl.runtime.*`` span (garbage collection);
  ``unattributed`` ``vfl.conductor.step`` itself, or no program span;
  the four add up to the device's idle time;
* host wait: time in which a ``sync_*`` span is open on some thread and
  the device is busy: the host waits for the chip;
* every device counts alike: each number is the mean over the devices.
"""
from __future__ import annotations

import dataclasses
import heapq

from bench import traces

PROGRAM_PREFIX = "vfl."
STEP = "vfl.conductor.step"
CATEGORIES = ("protocol", "crypto_host", "gc", "unattributed")
_LAYER_CATEGORY = {"conductor": "protocol", "protocol": "protocol",
                   "transport": "protocol", "crypto": "crypto_host",
                   "runtime": "gc"}


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    dur_ns: float
    thread: int           # index of the host thread's line in the trace

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def program_spans(path: str) -> list[Span]:
    """The host events of an ``.xplane.pb`` whose names start with
    ``vfl.``, each with its thread line.  A TraceMe metadata suffix
    (``name#k=v,…#``) is cut off the name where the trace keeps one."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name != traces.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    out.append(Span(e.name.split("#", 1)[0], e.start_ns,
                                    e.duration_ns, i))
    return out


def category(name: str) -> str:
    """The idle-time category of a program span's name."""
    if name == STEP:
        return "unattributed"
    layer = name.split(".", 2)[1] if name.count(".") >= 2 else ""
    return _LAYER_CATEGORY.get(layer, "unattributed")


def _innermost_segments(spans, t0: float, t1: float):
    """[(a, b, span | None)]: [t0, t1) cut at every span edge, each piece
    with the span that started last among those open over it."""
    edges = []
    for i, s in enumerate(spans):
        a, b = max(s.start_ns, t0), min(s.end_ns, t1)
        if b > a:
            edges.append((a, 1, i))
            edges.append((b, 0, i))
    edges.sort()
    segs, heap, closed, x = [], [], set(), t0
    for t, opening, i in edges:
        if t > x:
            while heap and heap[0][2] in closed:
                heapq.heappop(heap)
            segs.append((x, t, spans[heap[0][2]] if heap else None))
            x = t
        if opening:
            heapq.heappush(heap, (-spans[i].start_ns, -i, i))
        else:
            closed.add(i)
    if t1 > x:
        segs.append((x, t1, None))
    return segs


def _idle(ops, t0: float, t1: float) -> tuple[list, list]:
    """(busy, idle) intervals of one device's operations within
    [t0, t1), each merged and sorted."""
    busy = traces._union(c for c in (traces._clip(e, t0, t1) for e in ops)
                         if c is not None)
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return busy, idle


def _overlap(xs, ys) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    total, j = 0.0, 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            total += max(0.0, min(b, ys[k][1]) - max(a, ys[k][0]))
            k += 1
    return total


@dataclasses.dataclass
class Attribution:
    window_s: float
    idle_s: dict[str, float]    # per category (`CATEGORIES`)
    host_wait_s: float
    busy_s: float

    def share(self, key: str) -> float:
        """An idle category or ``host_wait`` over the window."""
        s = self.host_wait_s if key == "host_wait" else self.idle_s[key]
        return s / self.window_s


def _by_span(devices, spans, t0: float, t1: float, busy: bool):
    segs = _innermost_segments(spans, t0, t1)
    names = sorted({s.name if s else "" for _, _, s in segs})
    cover = {nm: traces._union((a, b) for a, b, s in segs
                               if (s.name if s else "") == nm)
             for nm in names}
    n_dev = max(len(devices), 1)
    out = dict.fromkeys(names, 0.0)
    for ops in devices:
        part = _idle(ops, t0, t1)[0 if busy else 1]
        for nm in names:
            out[nm] += _overlap(part, cover[nm]) * 1e-9 / n_dev
    return out


def idle_by_span(devices: list[list[traces.Event]], spans: list[Span],
                 t0: float, t1: float) -> dict[str, float]:
    """Idle seconds of the devices in [t0, t1) per innermost span name
    (mean over the devices; '' where no program span is open)."""
    return _by_span(devices, spans, t0, t1, busy=False)


def busy_by_span(devices: list[list[traces.Event]], spans: list[Span],
                 t0: float, t1: float) -> dict[str, float]:
    """Busy seconds of the devices in [t0, t1) per innermost span name:
    where the host is while the chip works."""
    return _by_span(devices, spans, t0, t1, busy=True)


def attribute(devices: list[list[traces.Event]], spans: list[Span],
              t0: float, t1: float) -> Attribution:
    """Book every device's idle time in [t0, t1) to the innermost
    program span's layer, and measure the host's wait on a busy device
    (see the module docstring)."""
    idle_s = dict.fromkeys(CATEGORIES, 0.0)
    for nm, sec in idle_by_span(devices, spans, t0, t1).items():
        idle_s[category(nm) if nm else "unattributed"] += sec
    syncs = traces._union(
        c for c in (traces._clip(s, t0, t1) for s in spans
                    if s.name.rsplit(".", 1)[-1].startswith("sync_"))
        if c is not None)
    n_dev = max(len(devices), 1)
    wait = busy_s = 0.0
    for ops in devices:
        busy = _idle(ops, t0, t1)[0]
        busy_s += sum(b - a for a, b in busy) * 1e-9 / n_dev
        wait += _overlap(syncs, busy) * 1e-9 / n_dev
    return Attribution(window_s=(t1 - t0) * 1e-9, idle_s=idle_s,
                       host_wait_s=wait, busy_s=busy_s)


def gaps(devices: list[list[traces.Event]], annotations, spans,
         t0: float, t1: float, n: int = 10) -> list[tuple[str, float,
                                                         float]]:
    """The `n` longest idle intervals of the devices in [t0, t1), longest
    first, as (label, seconds, start in seconds after t0).  The label is
    the innermost of the harness's call annotations and the program
    spans open at the gap's middle, or `traces.HOST_WORK`."""
    calls = [a for a in annotations if a.name != traces.WINDOW]
    marks = [*calls, *spans]
    idle = [ab for ops in devices for ab in _idle(ops, t0, t1)[1]]
    idle.sort(key=lambda ab: ab[0] - ab[1])

    def label(a: float, b: float) -> str:
        mid = (a + b) / 2
        best = None
        for m in marks:         # the latest start; on a tie, the later mark
            if m.start_ns <= mid < m.end_ns and (
                    best is None or m.start_ns >= best.start_ns):
                best = m
        return best.name if best else traces.HOST_WORK

    return [(label(a, b), (b - a) * 1e-9, (a - t0) * 1e-9)
            for a, b in idle[:n]]
