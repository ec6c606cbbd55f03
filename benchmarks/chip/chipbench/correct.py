"""The comparison that decides ``correct``.

After the window has closed, a sample of the sampled requests that
finished, drawn from the seed, is run through the configuration's plain
reference: each prompt followed by the tokens it was served.  The sample
always holds the request that served the most tokens, the one with the
longest prompt, and the one that served most of each route: prefilled on
a prefiller and handed over by a KV transfer, or prefilled on a
Convertible Decoder.  The number compared is the widest gap by which a
served token's reference logit lies below the reference's best logit at
that position.
Every token served greedily at the configuration's precision lies within
rounding of the best, so the gap stays small; a token computed wrongly, or
in a lower precision, lies further down.

The control (``control=True``) puts the reference in the program's place in
float8: at the same positions of the same rows it reads the gap of the token
that the float8 reference ranks first.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.traffic import seed_rng


@dataclass
class Row:
    prompt: np.ndarray
    served: list
    route: str = ""                 # "prefiller" | "convertible"


def pick_sample(rows: list[Row], seed: int, min_tokens: int,
                max_rows: int) -> list[Row]:
    """The rows with most served tokens, with the longest prompt and with
    most served tokens on each route, then others in an order drawn from
    the seed, until `min_tokens` served tokens or `max_rows` rows are in."""
    if not rows:
        return []
    order = list(seed_rng(seed, 7).permutation(len(rows)))
    idx = range(len(rows))
    first = [max(idx, key=lambda i: len(rows[i].served)),
             max(idx, key=lambda i: len(rows[i].prompt))]
    for route in sorted({r.route for r in rows}):
        first.append(max((i for i in idx if rows[i].route == route),
                         key=lambda i: len(rows[i].served)))
    out: list[int] = []
    for i in first + order:
        if i in out:
            continue
        if len(out) >= max_rows or (
                len(out) >= len(first)
                and sum(len(rows[j].served) for j in out) >= min_tokens):
            break
        out.append(int(i))
    return [rows[i] for i in out]


@jax.jit
def _gaps(lg, served, mask):
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, served[..., None], axis=-1)[..., 0]
    return jnp.where(mask, best - got, 0.0)


@jax.jit
def _control_gaps(lg, lg8, mask):
    pick = jnp.argmax(lg8, axis=-1)
    return _gaps(lg, pick, mask)


def widest_gap(ref, spec: dict, params, rows: list[Row], seq_len: int,
               positions: int, block_rows: int, control: bool = False
               ) -> tuple[float, Optional[float], int]:
    """(widest gap of the served tokens, widest gap of the float8 control or
    None, served tokens compared).  Rows are padded to `seq_len` tokens and
    `positions` read positions, so every block has one shape."""
    widest, widest8, n = 0.0, (0.0 if control else None), 0
    for b0 in range(0, len(rows), block_rows):
        blk = rows[b0:b0 + block_rows]
        toks = np.zeros((block_rows, seq_len), np.int32)
        idx = np.zeros((block_rows, positions), np.int32)
        served = np.zeros((block_rows, positions), np.int32)
        mask = np.zeros((block_rows, positions), bool)
        for r, row in enumerate(blk):
            L, m = len(row.prompt), len(row.served)
            seq = np.concatenate([row.prompt, np.asarray(row.served[:-1],
                                                         np.int32)])
            toks[r, :len(seq)] = seq
            idx[r, :m] = np.arange(L - 1, L - 1 + m)
            served[r, :m] = row.served
            mask[r, :m] = True
            n += m
        toks_d, idx_d, mask_d = map(jnp.asarray, (toks, idx, mask))
        lg = ref.logits_at(spec, params, toks_d, idx_d, "f32")
        widest = max(widest, float(jnp.max(
            _gaps(lg, jnp.asarray(served), mask_d))))
        if control:
            lg8 = ref.logits_at(spec, params, toks_d, idx_d, "fp8")
            widest8 = max(widest8, float(jnp.max(
                _control_gaps(lg, lg8, mask_d))))
            del lg8
        del lg
    return widest, widest8, n
