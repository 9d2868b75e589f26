"""Reduction of a profiler trace to the benchmark's per-layer numbers.

`load` reads an ``.xplane.pb`` with `jax.profiler.ProfileData` into plain
events: the operations on each device's "XLA Ops" line, and the
harness's own host annotations (names starting with `PREFIX`).  `reduce`
works on those events alone, so it is tested on synthetic ones.

Definitions, over the traced window [t0, t1) (the ``bench.window``
annotation):

* busy_s:  length of the union of a device's operation intervals, mean
           over the devices; idle share = 1 − busy_s / window_s;
* kernel time: summed durations of the operations of a Pallas kernel:
           the HLO instructions that carry its ``pallas_call(name=...)``;
* glue:    summed durations of operations that are no kernel, over the
           summed durations of all operations;
* host:    share of the window in which the device is idle and the
           host is outside every backend-call annotation: the conductor
           and the protocol legs holding the chip back (device time the
           host merely waits on inside or after a call is not counted);
* gaps:    idle intervals of the device, each labelled by the innermost
           annotation that covers its middle, or `HOST_WORK`.
"""
from __future__ import annotations

import dataclasses
import glob
import os

PREFIX = "bench."
WINDOW = PREFIX + "window"
HOST_WORK = "protocol host work"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    op_s: dict[str, float]           # per operation name, mean over devices
    kernel_s: dict[str, float]       # per kernel, mean over devices
    glue_share: float | None         # None when no operation ran
    host_share: float
    gaps: list[tuple[str, float]]    # longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb under {log_dir}")
    return paths[0]


def op_name(text: str) -> str:
    """Short name of a device operation.  On a TPU an operation's event
    name is its HLO instruction text, ``%name = shape op(operands…)``:
    keep the name and the result shape, drop the operands, which name
    other instructions."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text
    shape = "(tuple)" if rest.startswith("(") else \
        rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head.lstrip('%')} {shape}"


def kernel_of(name: str, kernels) -> str:
    """The kernel whose ``pallas_call(name=...)`` names this operation
    (an instruction named ``rns_he_matvec`` or ``rns_he_matvec.3``), or
    ''."""
    base = name.split(" ", 1)[0].split(".", 1)[0]
    return base if base in kernels else ""


def load(path: str, kernels) -> tuple[list[list[Event]],
                                      list[Event], dict[str, str]]:
    """Device operations per device (by `op_name`), host annotations, and
    the kernel (or '') of every operation name."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, annotations, kind = [], [], {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name = op_name(e.name)
                    if name not in kind:
                        kind[name] = kernel_of(name, kernels)
                    ops.append(Event(name, e.start_ns, e.duration_ns))
            devices.append(ops)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        annotations.append(
                            Event(e.name, e.start_ns, e.duration_ns))
    return devices, annotations, kind


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(e: Event, t0: float, t1: float) -> tuple[float, float] | None:
    a, b = max(e.start_ns, t0), min(e.end_ns, t1)
    return (a, b) if b > a else None


def _uncovered(spans, cover) -> float:
    """Length of the merged, sorted `spans` that the merged, sorted
    `cover` leaves uncovered."""
    total, j = 0.0, 0
    for a, b in spans:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        x, k = a, j
        while x < b:
            if k < len(cover) and cover[k][0] < b:
                total += max(cover[k][0] - x, 0.0)
                x = max(x, cover[k][1])
                k += 1
            else:
                total += b - x
                x = b
    return total


def reduce(devices: list[list[Event]], annotations: list[Event],
           kind: dict[str, str], n_gaps: int = 10) -> Summary:
    """Reduce one traced window (see the module docstring)."""
    wins = [a for a in annotations if a.name == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"{len(wins)} {WINDOW} annotations in the trace")
    t0, t1 = wins[0].start_ns, wins[0].end_ns
    calls = [a for a in annotations if a.name != WINDOW]
    n_dev = max(len(devices), 1)
    covered = _union(c for c in (_clip(a, t0, t1) for a in calls)
                     if c is not None)
    busy = host = 0.0
    op_s: dict[str, float] = {}
    idle: list[tuple[float, float]] = []
    for ops in devices:
        spans = []
        for e in ops:
            c = _clip(e, t0, t1)
            if c is None:
                continue
            spans.append(c)
            op_s[e.name] = op_s.get(e.name, 0.0) + (c[1] - c[0]) / n_dev
        merged = _union(spans)
        busy += sum(b - a for a, b in merged) / n_dev
        edges = [t0] + [x for ab in merged for x in ab] + [t1]
        free = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        host += _uncovered(free, covered) / n_dev
        idle += free
    kernel_s: dict[str, float] = {}
    for name, s in op_s.items():
        k = kind.get(name, "")
        if k:
            kernel_s[k] = kernel_s.get(k, 0.0) + s
    total = sum(op_s.values())
    glue = (total - sum(kernel_s.values())) / total if total > 0 else None

    def label(a: float, b: float) -> str:
        mid = (a + b) / 2
        inside = [c for c in calls if c.start_ns <= mid < c.end_ns]
        return max(inside, key=lambda c: c.start_ns).name if inside \
            else HOST_WORK

    idle.sort(key=lambda ab: ab[0] - ab[1])
    gaps = [(label(a, b), (b - a) * 1e-9) for a, b in idle[:n_gaps]]
    window_ns = t1 - t0
    return Summary(
        window_s=window_ns * 1e-9, busy_s=busy * 1e-9,
        op_s={k: v * 1e-9 for k, v in op_s.items()},
        kernel_s={k: v * 1e-9 for k, v in kernel_s.items()},
        glue_share=glue, host_share=host / window_ns, gaps=gaps)


def breakdown(s: Summary, n: int = 10) -> dict:
    """The result line's ``breakdown``: top device operations by time
    and the longest idle gaps by what the host was doing."""
    ops = sorted(s.op_s.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in s.gaps[:n]]}
