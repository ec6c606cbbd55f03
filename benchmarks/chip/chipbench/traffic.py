"""The open-loop traffic generator: one mix file in, a schedule out.

A mix (``mixes/<traffic>.json``) lists streams.  Each stream takes a share of
the cell's request rate and has an arrival process and two length laws, one
for prompts and one for new tokens.  The cell file (``cells/<workload>.json``)
fixes the rate.

The schedule covers three phases on one clock: warm-up (set-up, so that the
burst detector has a baseline and slots are occupied), the measured window,
and the drain.  It is one stratified draw from the mix's laws, the same for
every ``--seed``: lengths are the law's quantiles at (i + 0.5) / n, Poisson
gaps the exponential law's quantiles, and ON/OFF phases the quantiles of
their durations and rate multipliers; the mix's ``order_seed`` pairs and
orders them.  So a run replays the same trace whatever its seed, as a trace
replay would, and the spread of runs measures the system rather than the
draw.  ``--seed`` draws the prompts' token ids (and, elsewhere, the
weights).

Arrival kinds:

- ``poisson``: exponential gaps at the stream's rate.
- ``onoff``: a Poisson stream whose rate is modulated by alternating OFF
  and ON phases (exponential durations with means ``off_mean_s`` and
  ``on_mean_s``; each ON phase multiplies the base rate by a factor uniform
  in [``mult_min``, ``mult_max``]).  The base rate is set so that each
  schedule phase carries the stream's mean rate.

Lengths are ``lognormal`` by ``mean`` and ``sigma``, rounded and clipped to
[``min``, ``max``].  ``cut`` lowers each stream's longest prompt so that
prompt and output fit ``max_len``.
"""
from __future__ import annotations

import copy
import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Arrival:
    """One request of the schedule."""
    rid: int
    due: float                 # seconds on the schedule's clock
    prompt: np.ndarray         # (L,) int32 token ids
    max_new: int
    stream: int
    phase: str                 # "warmup" | "window" | "drain"


@dataclass
class Schedule:
    arrivals: list[Arrival]
    warmup_s: float
    window_s: float
    drain_s: float
    rate_rps: float

    @property
    def window_start(self) -> float:
        return self.warmup_s

    @property
    def window_end(self) -> float:
        return self.warmup_s + self.window_s

    @property
    def end(self) -> float:
        return self.window_end + self.drain_s

    def sampled(self) -> list[Arrival]:
        return [a for a in self.arrivals if a.phase == "window"]


def seed_rng(seed: int, *salt: int) -> np.random.Generator:
    """A generator for any non-negative seed (64-bit ones too)."""
    return np.random.default_rng([int(seed), *salt])


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def quantile_lengths(law: dict, n: int) -> np.ndarray:
    """The law's quantiles at (i + 0.5) / n, rounded and clipped."""
    if n <= 0:
        return np.zeros((0,), np.int64)
    if law["kind"] != "lognormal":
        raise ValueError(f"unknown length law {law['kind']!r}")
    sigma = float(law["sigma"])
    median = law["mean"] * math.exp(-sigma ** 2 / 2.0)
    z = np.array([NormalDist().inv_cdf(float(p)) for p in _strata(n)])
    x = median * np.exp(sigma * z)
    return np.clip(np.rint(x), law["min"], law["max"]).astype(np.int64)


def _exp_quantiles(mean: float, n: int) -> np.ndarray:
    return -mean * np.log1p(-_strata(n))


def _poisson_times(n: int, start: float, dur: float,
                   rng: np.random.Generator) -> np.ndarray:
    """n arrivals in [start, start + dur): exponential gaps at the
    quantiles (i + 0.5) / n, ordered by `rng`, scaled to fill the span."""
    if n <= 0:
        return np.zeros((0,))
    gaps = rng.permutation(_exp_quantiles(1.0, n))
    gaps *= dur / gaps.sum()
    return start + np.cumsum(gaps) - gaps[0] / 2.0


def _onoff_times(rate: float, arr: dict, start: float, dur: float,
                 rng: np.random.Generator) -> list[float]:
    """ON/OFF-modulated Poisson arrivals over [start, start + dur): as many
    OFF/ON cycles as the mean cycle fits, their durations and multipliers at
    the laws' quantiles, paired and ordered by `rng`, scaled to fill the
    span; the base rate gives the span `rate` on average."""
    off_m, on_m = float(arr["off_mean_s"]), float(arr["on_mean_s"])
    k = max(1, round(dur / (off_m + on_m)))
    off = rng.permutation(_exp_quantiles(off_m, k))
    on = rng.permutation(_exp_quantiles(on_m, k))
    mult = rng.permutation(float(arr["mult_min"]) + _strata(k) * (
        float(arr["mult_max"]) - float(arr["mult_min"])))
    scale = dur / (off.sum() + on.sum())
    off, on = off * scale, on * scale
    base = rate * dur / (off.sum() + (on * mult).sum())
    out, t = [], start
    for d, m in [x for i in range(k)
                 for x in ((off[i], 1.0), (on[i], mult[i]))]:
        out += list(_poisson_times(round(base * m * d), t, d, rng))
        t += d
    return out


def _phase_of(t: float, warmup: float, window: float) -> str:
    if t < warmup:
        return "warmup"
    return "window" if t < warmup + window else "drain"


def cut(mix: dict, max_len: int) -> dict:
    """`mix` with each stream's longest prompt lowered, where it must be,
    so that its longest prompt and longest output fit `max_len`."""
    out = copy.deepcopy(mix)
    for st in out["streams"]:
        room = int(max_len) - int(st["output"]["max"])
        if room < int(st["prompt"]["min"]):
            raise ValueError(f"max_len {max_len} holds no request of the mix")
        st["prompt"]["max"] = min(int(st["prompt"]["max"]), room)
    return out


def generate(mix: dict, rate_rps: float, seed: int, window_s: float,
             vocab_size: int) -> Schedule:
    """The schedule of `mix` at `rate_rps` for one run with `seed`."""
    warm, drain = float(mix["warmup_s"]), float(mix["drain_s"])
    order = int(mix["order_seed"])
    bounds = {"warmup": (0.0, warm), "window": (warm, window_s),
              "drain": (warm + window_s, drain)}
    draws = []                              # (due, stream, phase)
    for si, st in enumerate(mix["streams"]):
        rate = rate_rps * float(st["share"])
        arr = st["arrivals"]
        for pi, (ph, (s0, dur)) in enumerate(bounds.items()):
            rng = seed_rng(order, 1, si, pi)
            if arr["kind"] == "poisson":
                times = _poisson_times(round(rate * dur), s0, dur, rng)
            elif arr["kind"] == "onoff":
                times = _onoff_times(rate, arr, s0, dur, rng)
            else:
                raise ValueError(f"unknown arrival kind {arr['kind']!r}")
            draws += [(float(t), si, _phase_of(t, warm, window_s))
                      for t in times]

    # lengths: the laws' quantiles per stream and phase, paired and dealt
    # in the mix's order
    lens: dict[tuple, list] = {}
    for si, st in enumerate(mix["streams"]):
        for pi, ph in enumerate(bounds):
            n = sum(1 for _, s, p in draws if s == si and p == ph)
            rng = seed_rng(order, 2, si, pi)
            pairs = list(zip(quantile_lengths(st["prompt"], n),
                             rng.permutation(quantile_lengths(st["output"],
                                                              n))))
            lens[(si, ph)] = [pairs[k] for k in rng.permutation(n)]
    draws.sort()
    arrivals = []
    tok_rng = seed_rng(seed, 3)
    for rid, (t, si, ph) in enumerate(draws):
        L, new = lens[(si, ph)].pop()
        prompt = tok_rng.integers(0, vocab_size, size=int(L),
                                  dtype=np.int64).astype(np.int32)
        arrivals.append(Arrival(rid, float(t), prompt, int(new), si, ph))
    return Schedule(arrivals, warm, float(window_s), drain, float(rate_rps))


def prompt_range(mix: dict) -> tuple[int, int]:
    """Shortest and longest prompt any stream of `mix` can draw."""
    return (min(int(s["prompt"]["min"]) for s in mix["streams"]),
            max(int(s["prompt"]["max"]) for s in mix["streams"]))


def longest_request(mix: dict) -> int:
    """Most positions one request can hold: prompt plus new tokens."""
    return max(int(s["prompt"]["max"]) + int(s["output"]["max"])
               for s in mix["streams"])


def max_output(mix: dict) -> int:
    """Most new tokens one request of `mix` can ask for."""
    return max(int(s["output"]["max"]) for s in mix["streams"])
