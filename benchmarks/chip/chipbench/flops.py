"""Operations and bytes of each kernel call and of each model step.

Counted from the configuration's published sizes (``Dims``, read from the
configuration file, not from the program) at each call's true lengths: the
least work the call needs, so padding and blocks read past a row's length
count against the kernel and never for it.  The cache and activations are
bfloat16 (2 bytes); a multiply-add is 2 operations.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable

BYTES = 2            # bfloat16 cache, queries and outputs


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def of(cls, spec: dict) -> "Dims":
        """From a configuration file's Hugging Face keys."""
        heads = int(spec["num_attention_heads"])
        d = int(spec["hidden_size"])
        return cls(layers=int(spec["num_hidden_layers"]), d_model=d,
                   heads=heads,
                   kv_heads=int(spec["num_key_value_heads"]),
                   head_dim=int(spec.get("head_dim") or d // heads),
                   d_ff=int(spec["intermediate_size"]),
                   vocab=int(spec["vocab_size"]))

    @property
    def layer_macs_per_token(self) -> int:
        """Multiply-adds of one layer's projections and FFN for one token."""
        d, q, kv = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return d * q + 2 * d * kv + q * d + 3 * d * self.d_ff


@dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0

    def __iadd__(self, other: "Cost") -> "Cost":
        self.flops += other.flops
        self.bytes += other.bytes
        return self

    def __mul__(self, k: float) -> "Cost":
        return Cost(self.flops * k, self.bytes * k)


def decode_attention(dims: Dims, cur_lens: Iterable[int]) -> Cost:
    """One layer's flash-decode call: each live row's query against its
    cache of cur_len + 1 positions (the new token included)."""
    c = Cost()
    qo = 2 * dims.heads * dims.head_dim * BYTES
    for n in cur_lens:
        keys = int(n) + 1
        c.flops += 4.0 * dims.heads * dims.head_dim * keys
        c.bytes += 2.0 * keys * dims.kv_heads * dims.head_dim * BYTES + qo
    return c


def _causal_keys(offset: int, n: int) -> int:
    """Query/key pairs of n queries at positions offset..offset+n-1."""
    return n * offset + n * (n + 1) // 2


def chunked_prefill_attention(dims: Dims, offset: int, n: int) -> Cost:
    """One layer's prefill call: n valid queries from `offset`, causal
    against the cache up to each query's position."""
    kv = 2.0 * (offset + n) * dims.kv_heads * dims.head_dim * BYTES
    qo = 2.0 * n * dims.heads * dims.head_dim * BYTES
    return Cost(4.0 * dims.heads * dims.head_dim * _causal_keys(offset, n),
                kv + qo)


def prefill_flops(dims: Dims, offset: int, n: int) -> float:
    """Model operations of prefilling n prompt tokens from `offset`: every
    layer, and the head for the last token only."""
    per_layer = (2.0 * dims.layer_macs_per_token * n
                 + 4.0 * dims.heads * dims.head_dim * _causal_keys(offset, n))
    return dims.layers * per_layer + 2.0 * dims.d_model * dims.vocab


def decode_flops(dims: Dims, cur_lens: Iterable[int]) -> float:
    """Model operations of one new token for each live row."""
    total = 0.0
    for n in cur_lens:
        per_layer = (2.0 * dims.layer_macs_per_token
                     + 4.0 * dims.heads * dims.head_dim * (int(n) + 1))
        total += dims.layers * per_layer + 2.0 * dims.d_model * dims.vocab
    return total


def least_seconds(cost: Cost, peak_flops: float, peak_bw: float
                  ) -> tuple[float, str]:
    """The roofline's least time for `cost`, and which bound sets it."""
    t_c, t_m = cost.flops / peak_flops, cost.bytes / peak_bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def calls_flops(dims: Dims, decode: Iterable, prefill: Iterable) -> float:
    """Model operations of recorded program calls: `decode` as (kind,
    cur_lens) and `prefill` as (kind, offset, n, final); a chunk that is not
    its prompt's last runs no head."""
    ops = sum(decode_flops(dims, lens) for _, lens in decode)
    for _, offset, n, final in prefill:
        ops += prefill_flops(dims, offset, n)
        if not final:
            ops -= 2.0 * dims.d_model * dims.vocab
    return ops
