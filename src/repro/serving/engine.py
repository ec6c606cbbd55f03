"""Slot-based continuous-batching inference engine.

One ``Engine`` == one prefiller / decoder / convertible-decoder *instance*
in TokenScale terms.  It wraps (cfg, params) with a fixed pool of request
slots backed by a preallocated per-slot cache/state (the TPU analogue of
vLLM's paged KV pool — slot-contiguous rather than paged, page granularity
traded for XLA-static shapes; see DESIGN.md).

Three programs, built once per config by ``programs``, jitted with their
state donated by ``jitted`` and shared by every instance of it:

  * ``_prefill``      whole-prompt prefill of one request (batch-1 state)
  * ``_decode``       one token for every active slot
  * ``_mixed``        the Convertible-Decoder step: decode for active slots
                      FUSED with one restricted prefill chunk written
                      straight into its slot (XLA compiles a single program
                      — decode's idle MXU cycles absorb the chunk, the TPU
                      analogue of the paper's chunked-prefill co-location)

Donation lets each program update the pooled cache in place: after a call
the engine holds only the state the program returned.

The SLO-aware chunk size / memory reservation policy that *drives* ``_mixed``
lives in ``repro.core.convertible``.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import decode_step, init_state, prefill


@dataclass(frozen=True)
class SamplingParams:
    """temperature == 0 -> greedy (the default keeps decoding exact)."""
    temperature: float = 0.0
    top_k: int = 0                     # 0 = no top-k truncation
    top_p: float = 1.0                 # 1.0 = no nucleus truncation
    seed: int = 0


def sample_token(logits: np.ndarray, sp: SamplingParams,
                 rng: np.random.RandomState) -> int:
    """Temperature -> top-k -> top-p -> categorical, on one logits row."""
    if sp.temperature <= 0.0:
        return int(np.argmax(logits))
    z = logits.astype(np.float64) / sp.temperature
    if sp.top_k:
        kth = np.partition(z, -sp.top_k)[-sp.top_k]
        z = np.where(z < kth, -np.inf, z)
    p = np.exp(z - z.max())
    p /= p.sum()
    if sp.top_p < 1.0:
        order = np.argsort(-p)
        csum = np.cumsum(p[order])
        cut = int(np.searchsorted(csum, sp.top_p) + 1)
        mask = np.zeros_like(p)
        mask[order[:cut]] = 1.0
        p = p * mask
        p /= p.sum()
    return int(rng.choice(len(p), p=p))


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (L,) int32
    max_new_tokens: int
    arrival_t: float = 0.0
    image_embeds: Optional[np.ndarray] = None
    sampling: SamplingParams = SamplingParams()
    # filled by the engine:
    slot: int = -1
    first_token_t: float = -1.0
    finish_t: float = -1.0
    output: list = field(default_factory=list)
    prefill_done: int = 0              # tokens prefilled so far (chunked)

    def __post_init__(self):
        self._rng = np.random.RandomState(
            (self.sampling.seed * 1009 + self.rid) % (2 ** 31 - 1))

    def pick(self, logits_row: np.ndarray) -> int:
        return sample_token(logits_row, self.sampling, self._rng)


def _state_batch_axis(path) -> int:
    """State leaves are (B, ...) under `prefix` and (num_blocks, B, ...)
    under `blocks` (stacked for the depth scan) — see params.state_leaves."""
    key = path[0].key if hasattr(path[0], "key") else str(path[0])
    return 1 if key == "blocks" else 0


def _write_slot(pool, one, slot):
    """Copy a batch-1 state tree into slot `slot` of the pooled state."""
    def per_leaf(path, c, u):
        ax = _state_batch_axis(path)
        return jax.lax.dynamic_update_slice_in_dim(
            c, u.astype(c.dtype), slot, axis=ax)
    return jax.tree_util.tree_map_with_path(per_leaf, pool, one)


def _read_slot(pool, slot):
    """Extract a batch-1 view of `slot` from the pooled state tree."""
    def per_leaf(path, c):
        ax = _state_batch_axis(path)
        return jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=ax)
    return jax.tree_util.tree_map_with_path(per_leaf, pool)


@functools.lru_cache(maxsize=None)
def programs(cfg: ModelConfig):
    """The three programs of every instance serving `cfg`, as functions of
    (params, state, ...) returning the new state after the logits.

    Built once per config and shared, so an instance the autoscaler boots
    reuses what earlier instances traced and compiled for the same shapes."""

    def _prefill_one(params, state1, tokens, length, image_embeds, start):
        return prefill(cfg, params, state1, tokens, length,
                       image_embeds=image_embeds, start=start)

    def _decode_all(params, state, last_tokens, cur_lens):
        return decode_step(cfg, params, state, last_tokens, cur_lens)

    def _mixed(params, state, last_tokens, cur_lens,
               slot, chunk_tokens, chunk_len, chunk_start):
        """Fused decode + restricted prefill chunk (single XLA program).

        `slot` (1,) is the chunk's row of the pool: its decode row is
        padding, which writes nothing, and the chunk fills it in place."""
        rows = jnp.arange(last_tokens.shape[0], dtype=jnp.int32)
        logits, state = decode_step(
            cfg, params, state, last_tokens, cur_lens,
            slots=jnp.where(rows == slot, rows.shape[0], rows))
        clog, state = prefill(cfg, params, state, chunk_tokens, chunk_len,
                              start=chunk_start, slots=slot)
        return logits, state, clog

    return _prefill_one, _decode_all, _mixed


@functools.lru_cache(maxsize=None)
def jitted(program):
    """`program` jitted with its state (argument 1) donated, so it updates
    the cache in place.  One jitted function per program, shared."""
    return jax.jit(program, donate_argnums=1)


class Engine:
    """A single inference instance with `num_slots` concurrent requests."""

    def __init__(self, cfg: ModelConfig, params, num_slots: int = 8,
                 max_len: int = 256, chunk_size: int = 0):
        self.cfg = cfg
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.chunk_size = chunk_size          # >0 enables convertible mode
        self.state = init_state(cfg, num_slots, max_len)
        self.cur_lens = np.zeros((num_slots,), np.int32)
        self.active = np.zeros((num_slots,), bool)
        self.last_tokens = np.zeros((num_slots,), np.int32)
        self.slot_req: list[Optional[Request]] = [None] * num_slots
        self.waiting: list[Request] = []
        self.pending_chunked: Optional[Request] = None
        self.now = 0.0                        # virtual clock (tests/sim)
        self.steps = {"decode": 0, "mixed": 0}
        self._prefill, self._decode, self._mixed = map(jitted,
                                                       programs(cfg))

    # ------------------------------------------------------------------
    def free_slots(self) -> int:
        return int((~self.active).sum())

    def memory_tokens_used(self) -> int:
        return int(self.cur_lens[self.active].sum())

    def insert_prefilled(self, req: Request, payload, first_token: int,
                         stats=None) -> bool:
        """PD-disaggregation entry point: admit a request whose prefill ran
        on ANOTHER instance; `payload` is the kvtransfer.KVPayload."""
        from repro.serving import kvtransfer
        if self.free_slots() == 0:
            return False
        slot = self._alloc_slot(req)
        t0 = time.perf_counter()
        nbytes = kvtransfer.payload_bytes(payload)
        self.state = kvtransfer.insert(self.cfg, self.state, payload, slot)
        if stats is not None:
            stats.record(nbytes, payload.length, time.perf_counter() - t0)
        self.last_tokens[slot] = first_token
        self.cur_lens[slot] = payload.length
        req.prefill_done = payload.length
        if req.first_token_t < 0:
            req.first_token_t = self.now
        req.output.append(first_token)
        return True

    def add_request(self, req: Request) -> bool:
        """Admit a request; prefill immediately (or queue for chunking)."""
        if self.free_slots() == 0:
            self.waiting.append(req)
            return False
        if self.chunk_size and self.pending_chunked is None \
                and len(req.prompt) > self.chunk_size:
            # convertible decoder: long prompts prefill chunk-by-chunk
            req.slot = self._alloc_slot(req)
            self.pending_chunked = req
            return True
        self._prefill_now(req)
        return True

    def _alloc_slot(self, req: Request) -> int:
        slot = int(np.argmax(~self.active))
        self.active[slot] = True
        self.slot_req[slot] = req
        self.cur_lens[slot] = 0
        return slot

    def _prefill_now(self, req: Request):
        slot = self._alloc_slot(req)
        L = len(req.prompt)
        assert L <= self.max_len, (L, self.max_len)
        pad = min(max(8, int(2 ** np.ceil(np.log2(max(L, 1))))),
                  self.max_len)
        toks = np.zeros((1, pad), np.int32)
        toks[0, :L] = req.prompt
        st1 = init_state(self.cfg, 1, self.max_len)
        ie = None
        if req.image_embeds is not None:
            ie = jnp.asarray(req.image_embeds[None])
        logits, st1 = self._prefill(
            self.params, st1, jnp.asarray(toks),
            jnp.array([L], jnp.int32), ie, jnp.zeros((1,), jnp.int32))
        self.state = _write_slot(self.state, st1, slot)
        tok = req.pick(np.asarray(logits[0]))
        self.last_tokens[slot] = tok
        self.cur_lens[slot] = L
        req.prefill_done = L
        req.first_token_t = self.now
        req.output.append(tok)

    # ------------------------------------------------------------------
    def step(self) -> list[tuple[int, int]]:
        """One engine iteration.  Returns [(rid, token)] emitted."""
        emitted: list[tuple[int, int]] = []
        if not self.active.any() and self.pending_chunked is None:
            self._drain_waiting()
            return emitted

        if self.pending_chunked is not None:
            emitted += self._step_mixed()
            self.steps["mixed"] += 1
        elif self.active.any():
            emitted += self._step_decode()
            self.steps["decode"] += 1
        self._drain_waiting()
        return emitted

    def _drain_waiting(self):
        while self.waiting and self.free_slots() > 0:
            self.add_request(self.waiting.pop(0))

    def _step_decode(self) -> list[tuple[int, int]]:
        logits, self.state = self._decode(
            self.params, self.state,
            jnp.asarray(self.last_tokens), jnp.asarray(self.cur_lens))
        return self._commit_decode(logits)

    def _step_mixed(self) -> list[tuple[int, int]]:
        req = self.pending_chunked
        C = self.chunk_size
        start = req.prefill_done
        L = len(req.prompt)
        chunk = np.zeros((1, C), np.int32)
        n = min(C, L - start)
        chunk[0, :n] = req.prompt[start:start + n]
        slot = req.slot
        logits, self.state, clog = self._mixed(
            self.params, self.state,
            jnp.asarray(self.last_tokens), jnp.asarray(self.cur_lens),
            jnp.array([slot], jnp.int32), jnp.asarray(chunk),
            jnp.array([min(L, start + n)], jnp.int32),
            jnp.array([start], jnp.int32))
        req.prefill_done += n
        out = self._commit_decode(logits, skip_slot=slot)
        if req.prefill_done >= L:
            tok = req.pick(np.asarray(clog[0]))
            self.last_tokens[slot] = tok
            self.cur_lens[slot] = L
            req.first_token_t = self.now
            req.output.append(tok)
            self.pending_chunked = None
        return out

    def _commit_decode(self, logits, skip_slot: int = -1):
        emitted = []
        lg = np.asarray(logits)
        for s in range(self.num_slots):
            if not self.active[s] or s == skip_slot:
                continue
            req = self.slot_req[s]
            if req is None or req.prefill_done < len(req.prompt):
                continue
            tok = req.pick(lg[s])
            self.cur_lens[s] += 1
            self.last_tokens[s] = tok
            req.output.append(tok)
            emitted.append((req.rid, tok))
            if len(req.output) >= req.max_new_tokens \
                    or self.cur_lens[s] + 1 >= self.max_len:
                req.finish_t = self.now
                self.active[s] = False
                self.slot_req[s] = None
        return emitted

    # ------------------------------------------------------------------
    def run_until_drained(self, max_steps: int = 10_000):
        steps = 0
        while (self.active.any() or self.waiting
               or self.pending_chunked is not None):
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("engine did not drain")


