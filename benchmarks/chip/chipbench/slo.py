"""The SLO table and the end-to-end metric arithmetic.

The SLOs are TokenScale's (section V of the paper, after DynamoLLM and
MLPerf): time to first token 250 ms for prompts under 256 tokens, 400 ms
under 1,024 and 2,000 ms from 1,024 on; 100 ms per output token.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence

TPOT_SLO_S = 0.100


def ttft_slo_s(prompt_len: int) -> float:
    if prompt_len < 256:
        return 0.250
    if prompt_len < 1024:
        return 0.400
    return 2.000


@dataclass
class Outcome:
    """What the harness saw of one sampled request."""
    prompt_len: int
    max_new: int
    due: float                        # seconds, schedule clock
    first_t: Optional[float] = None   # first token reached the host
    last_t: Optional[float] = None    # last token reached the host
    tokens: int = 0                   # tokens that reached the host
    ended_short: bool = False         # let go of before all its tokens

    @property
    def finished(self) -> bool:
        return self.tokens >= self.max_new and self.last_t is not None

    @property
    def ttft(self) -> float:
        if self.first_t is None or not self.finished:
            return math.inf
        return self.first_t - self.due

    @property
    def tpot(self) -> float:
        if not self.finished:
            return math.inf
        if self.tokens < 2:
            return 0.0
        return (self.last_t - self.first_t) / (self.tokens - 1)

    @property
    def met_slo(self) -> bool:
        return (self.finished and self.ttft <= ttft_slo_s(self.prompt_len)
                and self.tpot <= TPOT_SLO_S)


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank q-th percentile: the smallest value with at least q% of
    the sample at or below it.  Infinite values (failures) sort last."""
    if not values:
        return math.nan
    xs = sorted(values)
    k = max(math.ceil(q / 100.0 * len(xs)) - 1, 0)
    return xs[k]


def end_to_end(outcomes: Sequence[Outcome], window_tokens: int,
               window_s: float) -> dict:
    """The four end-to-end readings of one run, unrounded."""
    ttft = [o.ttft for o in outcomes]
    tpot = [o.tpot for o in outcomes]
    n = len(outcomes)
    met = sum(o.met_slo for o in outcomes)
    return {
        "ttft_p90_ms": 1e3 * percentile(ttft, 90),
        "tpot_p90_ms": 1e3 * percentile(tpot, 90),
        "slo_attain_pct": 100.0 * met / n if n else math.nan,
        "output_tok_s": window_tokens / window_s,
        "ttft_p50_ms": 1e3 * percentile(ttft, 50),
        "tpot_p50_ms": 1e3 * percentile(tpot, 50),
    }
