"""From a JAX profiler trace to the numbers the per-layer metrics read.

A traced run writes an ``.xplane.pb`` under its trace directory.  Three
things are taken from it, with JAX's own reader (``jax.profiler.
ProfileData``):

- the harness's host spans (``bench.submit``, ``bench.step``,
  ``bench.autoscale``, ``bench.idle``): ``TraceAnnotation`` events on a
  host plane;
- each device plane's operations (its ``XLA Ops`` line), named by the
  HLO instruction (``%decode_attention.6 = f32[...] custom-call(...)`` is
  kept as ``decode_attention.6``; a Pallas kernel's instruction carries the
  kernel's name).  The union of their intervals is the device's busy time.
  A loop's or call's operation spans the operations of its body, so a
  breakdown ranks operations by self time;
- each device plane's program runs (its ``XLA Modules`` line), named after
  the jitted function (``jit__decode_all(...)`` is kept as
  ``jit__decode_all``).

Times are nanoseconds on the trace's clock.  ``Reduced`` can be written to
and read from a small JSON file, which is how a trace recorded on the chip
is kept for the tests.
"""
from __future__ import annotations

import bisect
import glob
import json
from dataclasses import asdict, dataclass
from pathlib import Path

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Ev:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Span:
    name: str
    start: float
    end: float


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Reduced:
    """Host spans and device events of one traced window."""

    def __init__(self, spans: list[Span], ops: list[list[Ev]],
                 modules: list[list[Ev]]):
        self.spans = sorted(spans, key=lambda s: s.start)
        self._span_starts = [s.start for s in self.spans]
        self.ops = ops                      # per device
        self.modules = modules              # per device
        self._busy = [merge((e.start, e.end) for e in dev) for dev in ops]
        self._starts = [[s for s, _ in b] for b in self._busy]

    # ---- window -----------------------------------------------------
    @property
    def start(self) -> float:
        return self.spans[0].start if self.spans else 0.0

    @property
    def end(self) -> float:
        return max((s.end for s in self.spans), default=0.0)

    def window_s(self) -> float:
        return (self.end - self.start) / 1e9

    # ---- device busy ------------------------------------------------
    def _busy_one(self, d: int, a: float, b: float) -> float:
        busy, starts = self._busy[d], self._starts[d]
        k = max(bisect.bisect_right(starts, a) - 1, 0)
        total = 0.0
        while k < len(busy) and busy[k][0] < b:
            s, e = busy[k]
            total += max(0.0, min(e, b) - max(s, a))
            k += 1
        return total

    def busy_in(self, a: float, b: float) -> float:
        """Device-busy nanoseconds inside [a, b), averaged over devices."""
        if not self._busy:
            return 0.0
        return sum(self._busy_one(d, a, b)
                   for d in range(len(self._busy))) / len(self._busy)

    def busy_s(self) -> float:
        return self.busy_in(self.start, self.end) / 1e9

    # ---- programs and kernels ----------------------------------------
    def program_runs(self, names) -> list[Ev]:
        """Program runs whose name is one of `names`, inside the window."""
        names = tuple(names)
        return [e for dev in self.modules for e in dev
                if e.name in names and self.start <= e.start < self.end]

    def kernel_seconds(self, kernel: str) -> float:
        """Device seconds of the operations named after `kernel`, inside
        the window, averaged over devices."""
        if not self.ops:
            return 0.0
        t = sum(e.dur for dev in self.ops for e in dev
                if _is_kernel(e.name, kernel)
                and self.start <= e.start < self.end)
        return t / len(self.ops) / 1e9

    # ---- breakdown ---------------------------------------------------
    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, and the longest idle
        gaps, each named by the harness span it fell in."""
        per_op: dict[str, float] = {}
        for dev in self.ops:
            for e, own in zip(dev, self_times(dev)):
                if self.start <= e.start < self.end:
                    per_op[e.name] = per_op.get(e.name, 0.0) + own
        n = max(len(self.ops), 1)
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for busy in self._busy[:1]:
            edges = [self.start] + [x for s, e in busy
                                    for x in (s, e)] + [self.end]
            for a, b in zip(edges[0::2], edges[1::2]):
                a, b = max(a, self.start), min(b, self.end)
                if b > a:
                    gaps.append((b - a, self.span_at((a + b) / 2.0)))
        gaps.sort(key=lambda g: -g[0])
        return {"device_ops": [[k, v / n / 1e9] for k, v in ops],
                "idle_gaps": [[name, g / 1e9] for g, name in gaps[:top]]}

    def span_at(self, t: float) -> str:
        """Name of the harness span around time t ("between spans" if
        none; the harness's spans never nest)."""
        k = bisect.bisect_right(self._span_starts, t) - 1
        if k >= 0 and t < self.spans[k].end:
            return self.spans[k].name
        return "between spans"

    # ---- storage -----------------------------------------------------
    def to_json(self) -> dict:
        return {"spans": [asdict(s) for s in self.spans],
                "ops": [[asdict(e) for e in d] for d in self.ops],
                "modules": [[asdict(e) for e in d] for d in self.modules]}

    @classmethod
    def from_json(cls, obj: dict) -> "Reduced":
        return cls([Span(**s) for s in obj["spans"]],
                   [[Ev(**e) for e in d] for d in obj["ops"]],
                   [[Ev(**e) for e in d] for d in obj["modules"]])


def self_times(evs: list[Ev]) -> list[float]:
    """Each event's duration less the parts that events nested in it
    cover (events sorted by start; a nested event ends within its
    parent)."""
    own = [e.dur for e in evs]
    stack: list[int] = []
    for k, e in enumerate(evs):
        while stack and evs[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= e.dur
        stack.append(k)
    return own


def _is_kernel(op_name: str, kernel: str) -> bool:
    return op_name == kernel or op_name.startswith(kernel + ".")


def op_name(hlo: str) -> str:
    """``%fusion.93 = s32[64] fusion(...)`` -> ``fusion.93``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and "SparseCore" not in name


def reduce_profile(pd) -> Reduced:
    """``Reduced`` from a ``jax.profiler.ProfileData``."""
    spans, ops, modules = [], [], []
    for plane in pd.planes:
        if is_device_plane(plane.name):
            dev_ops, dev_mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev_ops += [Ev(op_name(e.name), e.start_ns,
                                   e.duration_ns) for e in line.events]
                elif line.name == MODULES_LINE:
                    dev_mods += [Ev(e.name.split("(", 1)[0], e.start_ns,
                                    e.duration_ns) for e in line.events]
            dev_ops.sort(key=lambda e: (e.start, -e.dur))
            ops.append(dev_ops)
            modules.append(dev_mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Span(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
    return Reduced(spans, ops, modules)


def reduce_dir(trace_dir: Path) -> Reduced:
    from jax.profiler import ProfileData
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_profile(ProfileData.from_file(found[-1]))


def save(red: Reduced, path: Path):
    with open(path, "w") as f:
        json.dump(red.to_json(), f, separators=(",", ":"))


def load(path: Path) -> Reduced:
    with open(path) as f:
        return Reduced.from_json(json.load(f))
