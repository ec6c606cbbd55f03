"""The seeded traffic generator and the SLO arithmetic of the chip
benchmark."""
import math
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import slo, traffic  # noqa: E402
from chipbench.discover import BENCH_DIR, load_json  # noqa: E402

CHAT = load_json(BENCH_DIR / "mixes" / "chat-steady.json")
BURST = load_json(BENCH_DIR / "mixes" / "burst-long.json")
BIG_SEED = 2 ** 31 + 12345


def _key(s):
    return [(a.rid, round(a.due, 9), a.prompt.tobytes(), a.max_new, a.phase)
            for a in s.arrivals]


def _work(s):
    return [(round(a.due, 9), len(a.prompt), a.max_new, a.phase)
            for a in s.arrivals]


@pytest.mark.parametrize("mix", [CHAT, BURST], ids=["chat", "burst"])
def test_same_seed_same_schedule(mix):
    a = traffic.generate(mix, 4.0, BIG_SEED, 30.0, 1000)
    b = traffic.generate(mix, 4.0, BIG_SEED, 30.0, 1000)
    assert _key(a) == _key(b)
    c = traffic.generate(mix, 4.0, BIG_SEED + 1, 30.0, 1000)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("mix", [CHAT, BURST], ids=["chat", "burst"])
def test_seeds_share_the_work(mix):
    """Another seed replays the same arrivals and sizes with other token
    ids; another order seed reorders the same sizes in every phase."""
    a = traffic.generate(mix, 5.0, 1, 30.0, 1000)
    b = traffic.generate(mix, 5.0, 99, 30.0, 1000)
    assert _work(a) == _work(b)
    assert [x.prompt.tobytes() for x in a.arrivals] != \
        [x.prompt.tobytes() for x in b.arrivals]
    c = traffic.generate({**mix, "order_seed": mix["order_seed"] + 1}, 5.0,
                         1, 30.0, 1000)

    def sizes(s):
        return {ph: (Counter(len(x.prompt) for x in s.arrivals
                             if x.phase == ph),
                     Counter(x.max_new for x in s.arrivals
                             if x.phase == ph))
                for ph in ("warmup", "window", "drain")}
    if mix["streams"][0]["arrivals"]["kind"] == "poisson":
        assert sizes(a) == sizes(c)
    assert _work(a) != _work(c)


def test_chat_rate_and_lengths_match_the_mix():
    rate, window = 6.0, 40.0
    s = traffic.generate(CHAT, rate, 3, window, 151936)
    win = s.sampled()
    assert len(win) == round(rate * window)
    assert all(s.window_start <= a.due < s.window_end for a in win)
    st = CHAT["streams"][0]
    lens = np.array([len(a.prompt) for a in win])
    outs = np.array([a.max_new for a in win])
    assert lens.min() >= st["prompt"]["min"]
    assert lens.max() <= st["prompt"]["max"]
    assert outs.min() >= st["output"]["min"]
    assert outs.max() <= st["output"]["max"]
    # quantile draws: the median of a lognormal with this mean and sigma
    for got, law in ((lens, st["prompt"]), (outs, st["output"])):
        median = law["mean"] * math.exp(-law["sigma"] ** 2 / 2)
        assert abs(np.median(got) - median) <= 0.02 * median
    # exponential gaps: coefficient of variation near 1
    gaps = np.diff([a.due for a in win])
    assert 0.8 < gaps.std() / gaps.mean() < 1.2
    assert all(0 <= t < 151936 for a in win for t in a.prompt[:4])


def test_onoff_bursts_carry_the_mean_rate():
    """ON phases run at 2-6x the OFF rate, about 47% of the time; each
    schedule phase carries the mean rate."""
    rate, window = 4.0, 50.0
    s = traffic.generate(BURST, rate, 5, window, 64000)
    for ph, dur in (("warmup", BURST["warmup_s"]), ("window", window),
                    ("drain", BURST["drain_s"])):
        n = sum(a.phase == ph for a in s.arrivals)
        assert abs(n - rate * dur) <= 0.05 * rate * dur + 2, ph
    due = np.array([a.due for a in s.sampled()])
    per_s = np.histogram(due, bins=np.arange(s.window_start,
                                             s.window_end + 1e-9, 1.0))[0]
    # bursty: counts per second spread more than a Poisson stream's
    chat = traffic.generate(CHAT, rate, 5, window, 64000)
    chat_s = np.histogram([a.due for a in chat.sampled()],
                          bins=np.arange(chat.window_start,
                                         chat.window_end + 1e-9, 1.0))[0]
    assert per_s.var() / per_s.mean() > 1.4
    assert per_s.var() > 1.4 * chat_s.var()


@pytest.mark.parametrize("max_len", [2048, 4096])
def test_cut_fits_every_request_into_max_len(max_len):
    for mix in (CHAT, BURST):
        c = traffic.cut(mix, max_len)
        assert traffic.longest_request(c) <= max_len
        assert c["streams"][0]["output"] == mix["streams"][0]["output"]
        s = traffic.generate(c, 4.0, 1, 20.0, 1000)
        assert max(len(a.prompt) + a.max_new for a in s.arrivals) <= max_len
    with pytest.raises(ValueError):
        traffic.cut(CHAT, 600)


def test_ttft_slo_table():
    assert slo.ttft_slo_s(255) == 0.25
    assert slo.ttft_slo_s(256) == 0.40
    assert slo.ttft_slo_s(1023) == 0.40
    assert slo.ttft_slo_s(1024) == 2.0


def test_metric_arithmetic():
    oc = [
        # met both: ttft 0.1 s, 9 gaps over 0.45 s -> 50 ms
        slo.Outcome(100, 10, due=1.0, first_t=1.1, last_t=1.55, tokens=10),
        # ttft 0.3 s misses the 250 ms SLO of a short prompt
        slo.Outcome(100, 5, due=2.0, first_t=2.3, last_t=2.5, tokens=5),
        # long prompt: ttft 1.5 s meets 2 s; tpot 0.2 s misses
        slo.Outcome(1500, 3, due=0.0, first_t=1.5, last_t=1.9, tokens=3),
        # unfinished: counts as a miss and as +inf in both tails
        slo.Outcome(300, 8, due=3.0, first_t=3.2, last_t=None, tokens=4),
    ]
    assert oc[0].ttft == pytest.approx(0.1)
    assert oc[0].tpot == pytest.approx(0.05)
    assert [o.met_slo for o in oc] == [True, False, False, False]
    assert math.isinf(oc[3].ttft) and math.isinf(oc[3].tpot)
    m = slo.end_to_end(oc, window_tokens=150, window_s=30.0)
    assert m["slo_attain_pct"] == 25.0
    assert m["output_tok_s"] == 5.0
    # nearest rank: p90 of 4 values is the 4th, an unfinished request
    assert math.isinf(m["ttft_p90_ms"]) and math.isinf(m["tpot_p90_ms"])
    assert m["ttft_p50_ms"] == pytest.approx(300.0)
    assert m["tpot_p50_ms"] == pytest.approx(50.0)


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert slo.percentile(xs, 90) == 90
    assert slo.percentile(xs, 50) == 50
    assert slo.percentile([5.0], 90) == 5.0
