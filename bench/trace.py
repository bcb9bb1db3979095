"""From a `jax.profiler` trace to the numbers the per-layer metrics read.

The run wraps each timed call in a `jax.profiler.TraceAnnotation` named
`bench.call` (its own span). `read_xplane` turns the trace file into
plain lists (device ops per chip, the benchmark's spans, the host's other
events); `Reading` holds what the readers in `bench/metrics/` use. All
times are nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

SPAN = "bench.call"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# ops that hold other ops: a scan's while loop, a cond; their events span
# those of the ops they run, so they count for busy time but not in the
# breakdown by op
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def op_name(event_name: str) -> str:
    """The HLO op's name of a TPU trace event. The trace names an op by
    its whole HLO instruction (`%fusion.26 = f32[...] fusion(...), ...`);
    the name is what precedes ` = `, without the `%`."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")

Event = Tuple[str, int, int]  # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    devices: Dict[int, List[Event]]  # chip id -> its ops, by start
    spans: List[Event]               # the benchmark's spans, by start
    host: List[Event]                # the host's other events


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def from_profile(pd) -> Trace:
    """A `jax.profiler.ProfileData` as a Trace."""
    devices: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    host: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(op_name(e.name), int(e.start_ns),
                             int(e.end_ns)) for e in line.events]
            devices[int(m.group(1))] = sorted(ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, int(e.start_ns), int(e.end_ns))
                    (spans if e.name == SPAN else host).append(ev)
    return Trace(devices, sorted(spans, key=lambda e: e[1]),
                 sorted(host, key=lambda e: e[1]))


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


# ------------------------------------------------------------ reduction
def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge overlapping (start, end) intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(events: List[Event], lo: int, hi: int) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def busy_ns(events: List[Event], lo: int, hi: int) -> int:
    """Time in [lo, hi) in which at least one of `events` runs."""
    return sum(e - s for s, e in union([(s, e) for _, s, e in
                                        clip(events, lo, hi)]))


def gaps(events: List[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The idle intervals of [lo, hi) between `events`."""
    out, t = [], lo
    for s, e in union([(s, e) for _, s, e in clip(events, lo, hi)]):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def matching_ns(events: List[Event], pattern: re.Pattern, lo: int,
                hi: int) -> int:
    """Busy time of the events whose name matches `pattern`."""
    return busy_ns([e for e in events if pattern.search(e[0])], lo, hi)


def host_label(host: List[Event], s: int, e: int) -> str:
    """What the host was doing in [s, e): the host event that overlaps it
    most, the shorter one on a tie."""
    best, key = "host: no event", (0, 0)
    for name, hs, he in host:
        if hs >= e:
            break
        ov = min(he, e) - max(hs, s)
        if ov > 0 and (ov, -(he - hs)) > key:
            best, key = name, (ov, -(he - hs))
    return best[:160]


@dataclasses.dataclass
class Reading:
    """What the per-layer readers see of one traced window."""

    trace: Trace
    chips: int
    rounds: int               # rounds run in the traced calls
    calls: int
    flops_per_round: float
    kernel_bytes_per_round: float
    peaks: dict
    # the kernels the cell's path runs: name -> pattern of their op names
    # (the configuration's `kernels`)
    kernels: Dict[str, str] = dataclasses.field(default_factory=dict)
    # the pattern of the op names of the collectives between the cell's
    # chips (the configuration's `collectives`), None on one chip
    collectives: Optional[str] = None

    def __post_init__(self):
        if not self.trace.spans:
            raise ValueError("the trace holds none of the benchmark's spans")
        self.lo = self.trace.spans[0][1]
        self.hi = self.trace.spans[-1][2]
        self.chip_ops = [self.trace.devices[i]
                         for i in sorted(self.trace.devices)][:self.chips]
        if len(self.chip_ops) < self.chips:
            raise ValueError(f"the trace holds {len(self.chip_ops)} TPU "
                             f"planes, the cell uses {self.chips}")
        self._kernels = {k: re.compile(p) for k, p in self.kernels.items()}
        for name in self._kernels:
            if not self.kernel_s(name):
                raise ValueError(
                    f"the trace holds no op of the {name} kernel "
                    f"(pattern {self.kernels[name]!r}) that the cell's "
                    f"path runs: its op names have changed, or the kernel "
                    f"left the path")
        self._collectives = (None if self.collectives is None
                             else re.compile(self.collectives))
        if self._collectives is not None and not self.collective_s():
            raise ValueError(
                f"the trace holds no collective op (pattern "
                f"{self.collectives!r}) though the cell's chips exchange "
                f"eq. (11): its op names have changed")

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def mean_ns(self, fn) -> float:
        vals = [fn(ops) for ops in self.chip_ops]
        return sum(vals) / len(vals)

    @property
    def busy_s(self) -> float:
        return self.mean_ns(lambda ops: busy_ns(ops, self.lo, self.hi)) * 1e-9

    def kernel_s(self, name: Optional[str] = None) -> Optional[float]:
        """Device seconds of the named kernel (of all the cell's kernels
        where `name` is None), mean over chips; None where the cell's
        path runs no such kernel."""
        if name is None:
            if not self._kernels:
                return 0.0
            pattern = re.compile("|".join(f"(?:{p})" for p in
                                          self.kernels.values()))
        elif name in self._kernels:
            pattern = self._kernels[name]
        else:
            return None
        return self.mean_ns(
            lambda ops: matching_ns(ops, pattern, self.lo, self.hi)) * 1e-9

    def collective_s(self) -> Optional[float]:
        """Device seconds of the collectives, mean over chips; None where
        the configuration names none."""
        if self._collectives is None:
            return None
        return self.mean_ns(lambda ops: matching_ns(
            ops, self._collectives, self.lo, self.hi)) * 1e-9

    def outside_s(self) -> float:
        """Device busy seconds outside the named kernels and collectives,
        mean over chips."""
        named = list(self.kernels.values())
        if self.collectives is not None:
            named.append(self.collectives)
        if not named:
            return self.busy_s
        pattern = re.compile("|".join(f"(?:{p})" for p in named))
        return self.busy_s - self.mean_ns(
            lambda ops: matching_ns(ops, pattern, self.lo, self.hi)) * 1e-9

    def idle_in_spans_s(self) -> float:
        """Device-idle seconds inside the benchmark's spans, mean over
        chips."""
        def idle(ops):
            return sum((e - s) - busy_ns(ops, s, e)
                       for _, s, e in self.trace.spans)
        return self.mean_ns(idle) * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (seconds, summed over the
        chips' mean) and the longest idle gaps of chip 0, named by what
        the host was doing."""
        per_name: Dict[str, float] = {}
        for ops in self.chip_ops:
            for name, s, e in clip(ops, self.lo, self.hi):
                if not CONTAINER.match(name):
                    per_name[name] = per_name.get(name, 0.0) + (e - s) * 1e-9
        ops = sorted(((n, t / self.chips) for n, t in per_name.items()),
                     key=lambda kv: -kv[1])[:top]
        idle = sorted(gaps(self.chip_ops[0], self.lo, self.hi),
                      key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, t] for n, t in ops],
                "idle_gaps": [[host_label(self.trace.host, s, e),
                               (e - s) * 1e-9] for s, e in idle]}


def collect(reading: Reading, readers: Dict[str, object]) -> Dict[str, float]:
    """Each reader's value; a reader that finds nothing returns None and
    its metric is left out."""
    out = {}
    for name, reader in readers.items():
        v: Optional[float] = reader.read(reading)
        if v is not None:
            out[name] = float(v)
    return out
