"""Operations and bytes of the chip benchmark's kernels and model steps,
against counts made by hand."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import flops  # noqa: E402
from chipbench.discover import BENCH_DIR, load_json  # noqa: E402

QWEN = flops.Dims.of(load_json(BENCH_DIR / "configs" / "qwen2-0.5b.json"))
YI = flops.Dims.of(load_json(BENCH_DIR / "configs" / "yi-9b-pp2.json"))


def test_dims_from_the_config_files():
    assert (QWEN.layers, QWEN.d_model, QWEN.heads, QWEN.kv_heads,
            QWEN.head_dim, QWEN.d_ff, QWEN.vocab) == \
        (24, 896, 14, 2, 64, 4864, 151936)
    assert (YI.layers, YI.head_dim, YI.kv_heads) == (24, 128, 4)
    # 4096*4096 (q) + 2*4096*512 (k, v) + 4096*4096 (o) + 3*4096*11008
    assert YI.layer_macs_per_token == 173_015_040
    assert QWEN.layer_macs_per_token == 14_909_440


def test_decode_attention_two_rows():
    # rows at cur_len 0 and 9 read 1 and 10 cached positions
    c = flops.decode_attention(QWEN, [0, 9])
    assert c.flops == 4 * 14 * 64 * (1 + 10)                 # 39,424
    # K and V: 2 * 11 positions * 2 heads * 64 * 2 B; q and out: 2 rows *
    # 2 * 14 * 64 * 2 B
    assert c.bytes == 2 * 11 * 2 * 64 * 2 + 2 * 2 * 14 * 64 * 2   # 12,800


def test_chunked_prefill_attention_chunk_at_offset():
    # three queries at positions 256, 257, 258 see 257 + 258 + 259 keys
    c = flops.chunked_prefill_attention(QWEN, 256, 3)
    assert c.flops == 4 * 896 * 774                          # 2,774,016
    assert c.bytes == 2 * 259 * 128 * 2 + 2 * 3 * 896 * 2    # 143,360
    whole = flops.chunked_prefill_attention(YI, 0, 2048)
    assert whole.flops == 4 * 4096 * (2048 * 2049 // 2)


def test_model_step_flops():
    # one Yi prompt token: 24 layers of 2 * macs + attention to itself,
    # and the head once
    assert flops.prefill_flops(YI, 0, 1) == 24 * (
        2 * 173_015_040 + 4 * 4096) + 2 * 4096 * 64000       # 8,829,403,136
    assert flops.decode_flops(QWEN, [0]) == 24 * (
        2 * 14_909_440 + 4 * 896) + 2 * 896 * 151936         # 988,008,448
    assert flops.decode_flops(QWEN, []) == 0.0


def test_least_seconds_names_its_bound():
    t, bound = flops.least_seconds(flops.Cost(197e12, 1.0), 197e12, 819e9)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = flops.least_seconds(flops.Cost(1.0, 819e9), 197e12, 819e9)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_step_mfu_prefill_counts_the_calls_that_prefilled():
    """Only the harness calls that ran a prefill count, with every program
    call inside them; a chunk that is not its prompt's last has no head."""
    from types import SimpleNamespace

    from chipbench.discover import load_module
    from chipbench.harness import Calls
    calls = Calls(prefill=[("prefill", 0, 100, True),
                           ("mixed", 0, 256, False)],
                  decode=[("decode", [5, 7]), ("mixed", [9]),
                          ("decode", [3])],
                  spans=[("bench.step", 0.010, 0, 1, 0, 1),
                         ("bench.step", 0.020, 1, 2, 1, 2),
                         ("bench.step", 0.500, 2, 2, 2, 3)])
    head = 2.0 * QWEN.d_model * QWEN.vocab
    ops = (flops.prefill_flops(QWEN, 0, 100) + flops.decode_flops(QWEN, [5, 7])
           + flops.prefill_flops(QWEN, 0, 256) - head
           + flops.decode_flops(QWEN, [9]))
    assert flops.calls_flops(QWEN, calls.decode[:2], calls.prefill) == ops
    peak = 197e12
    run = SimpleNamespace(calls=calls, dims=QWEN,
                          peaks={"bf16_flops_per_s": peak})
    mod = load_module(BENCH_DIR / "layer_metrics" / "step_mfu.prefill.py")
    assert mod.read(run) == pytest.approx(100.0 * ops / (0.030 * peak))
    assert mod.read(SimpleNamespace(calls=Calls(), dims=QWEN,
                                    peaks={"bf16_flops_per_s": peak})) is None
