"""Batched LM serving engine: continuous batching over per-slot decode state.

Decode runs over a fixed pool of B slots — the batch rows of one compiled
``decode_step``. Each slot owns its request's full decode state: a cursor
into the prompt, the last sampled token (both in the slot's
:class:`~repro.serve.slots.SlotEntry` ``state``), and — the piece that makes
re-fill possible — its OWN write position into the KV cache
(``attention.KVCache`` with vector ``pos``; ``transformer.per_slot_state``).
A request is admitted into a free slot, prefilled token-by-token through the
same cached decode path the dry-run lowers (bit-identical to serve_step),
decodes until EOS or budget, and retires per-slot; the freed row's cache
position resets to zero (``transformer.reset_slots`` — stale K/V above the
reset is hidden by the validity mask, no clearing needed) and the row
re-fills from the pending queue at the top of the next step, mid-flight,
while the other rows keep decoding. Because attention rows are independent,
a request's sampled tokens are identical whatever the batch composition —
continuous batching changes throughput, never outputs (greedy; pinned by
tests/test_serve_lm.py). On a TPU this holds between slot counts of 2 or
more only: a one-row decode matmul rounds differently (DESIGN.md §5.2).

Greedy and temperature sampling. Families whose decode state is not a
positional KV cache (ssm / hybrid recurrences, audio's per-request encoder
output) are served by the static wave path (``continuous=False`` semantics:
admission only into an idle pool); everything attention-shaped gets
continuous batching.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.data import tokenizer as tok
from repro.models import transformer as T
from repro.serve.slots import SlotPool


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 256
    temperature: float = 0.0        # 0 = greedy
    eos_id: int = tok.EOS
    seed: int = 0


@dataclasses.dataclass
class LMRequest:
    """One prompt's bookkeeping through the slot pool."""

    req_id: int
    prompt: np.ndarray              # (len,) int32 token ids
    max_new_tokens: int
    tokens: List[int] = dataclasses.field(default_factory=list)


class Engine:
    def __init__(self, params, cfg: ModelConfig, scfg: ServeConfig):
        self.params = params
        self.cfg = cfg
        self.scfg = scfg
        self._step = jax.jit(
            lambda p, st, t: T.decode_step(p, cfg, st, t))
        self._reset = jax.jit(T.reset_slots)
        # filler token for free slot rows: must be in-vocab — smoke configs
        # cap vocab below tok.PAD, and an out-of-vocab lookup embeds as NaN
        # (jnp.take fill), which a free row would write into its K/V cache.
        # Stale NaN survives a slot reset (0 * NaN in the probs @ V
        # contraction over masked positions), so the row poisons every
        # request admitted after it. A finite filler contributes exactly 0.
        self._fill = int(min(tok.PAD, cfg.vocab_size - 1))
        # throughput accounting for the last serve()/generate() call
        self.n_steps = 0

    @property
    def _per_slot_ok(self) -> bool:
        """Families whose decode state re-fills per slot (KV caches)."""
        return self.cfg.family not in ("ssm", "hybrid", "audio")

    def generate(self, prompts: List[np.ndarray],
                 max_new_tokens: int = 32,
                 frames: Optional[np.ndarray] = None) -> List[np.ndarray]:
        """Generate continuations for ``prompts``; results in order.

        Attention-family models route through :meth:`serve` with one slot
        per request (per-slot positions: each row prefills exactly its own
        prompt — no cross-row PAD positions in the cache). ssm / hybrid /
        audio keep the static lockstep path (:meth:`_generate_static`)."""
        if frames is not None or not self._per_slot_ok:
            return self._generate_static(prompts, max_new_tokens, frames)
        return self.serve(prompts, max_new_tokens, n_slots=len(prompts))

    def serve(self, prompts: List[np.ndarray], max_new_tokens: int = 32, *,
              n_slots: Optional[int] = None,
              continuous: bool = True) -> List[np.ndarray]:
        """Slot-based decode over ``n_slots`` rows; results in order.

        ``continuous=True`` re-fills freed slots from the pending queue
        mid-flight (the top of every step); ``continuous=False`` is the
        wave baseline — admission only when the pool has fully drained, so
        a batch's slowest request gates the next wave. Sampled tokens are
        identical either way under greedy decoding (per-row attention
        independence); only throughput differs.
        """
        scfg = self.scfg
        b = len(prompts) if n_slots is None else int(n_slots)
        if b < 1:
            raise ValueError(f"need at least one slot, got {b}")
        state = T.per_slot_state(
            T.init_serve_state(self.params, self.cfg, b, scfg.max_len), b)

        def on_admit(idx: int, entry) -> None:
            del idx
            # cursor into the prompt + last sampled token: the slot's
            # host-side decode state (the cache position lives in the
            # ServeState's per-slot pos, reset at admission below)
            entry.state = {"fed": 0, "last": int(tok.PAD)}

        pool: SlotPool[LMRequest, Dict[str, int]] = SlotPool(
            b, on_admit=on_admit)
        reqs = [
            LMRequest(req_id=i, prompt=np.asarray(p, np.int32).reshape(-1),
                      max_new_tokens=max_new_tokens)
            for i, p in enumerate(prompts)]
        for r in reqs:
            if r.prompt.size == 0:
                raise ValueError(f"empty prompt (request {r.req_id})")
            pool.submit(r)

        key = jax.random.PRNGKey(scfg.seed)
        self.n_steps = 0
        while pool.has_work:
            if continuous or pool.n_live == 0:
                admitted = pool.admit()
                if admitted:
                    free = np.zeros((b,), bool)
                    for idx, _ in admitted:
                        free[idx] = True
                    # re-filled rows restart at cache position 0; stale
                    # K/V above it is hidden by the pos-derived validity
                    # mask (attention._cache_valid), so no clearing
                    state = self._reset(state, jnp.asarray(free))
            tokens = np.full((b, 1), self._fill, np.int32)
            for idx, entry in pool.live():
                req, st = entry.item, entry.state
                tokens[idx, 0] = (req.prompt[st["fed"]]
                                  if st["fed"] < len(req.prompt)
                                  else st["last"])
            logits, state = self._step(self.params, state, tokens)
            self.n_steps += 1
            lg = np.asarray(logits, np.float32)
            if scfg.temperature > 0:
                key, k2 = jax.random.split(key)
                nxt = np.asarray(jax.random.categorical(
                    k2, jnp.asarray(lg) / scfg.temperature, axis=-1))
            else:
                nxt = lg.argmax(-1)
            for idx, entry in list(pool.live()):
                req, st = entry.item, entry.state
                st["fed"] += 1
                if st["fed"] < len(req.prompt):
                    continue            # mid-prefill: logits not sampled
                # this step consumed the final prompt token (first
                # generated token) or the previous sample (next one)
                t_new = int(nxt[idx])
                req.tokens.append(t_new)
                st["last"] = t_new
                if (t_new == scfg.eos_id
                        or len(req.tokens) >= req.max_new_tokens
                        or st["fed"] >= scfg.max_len):
                    pool.retire(idx)
        return [np.asarray(r.tokens, np.int32) for r in reqs]

    def _generate_static(self, prompts: List[np.ndarray],
                         max_new_tokens: int = 32,
                         frames: Optional[np.ndarray] = None
                         ) -> List[np.ndarray]:
        """Static lockstep batching (scalar cache positions): all prompts
        prefilled together left-aligned/PAD-masked, one token per step for
        every live slot, per-slot retirement without re-fill — the path
        for families whose decode state is not a per-row positional cache
        (ssm / hybrid / audio)."""
        b = len(prompts)
        scfg = self.scfg
        max_prompt = max(len(p) for p in prompts)
        state = T.init_serve_state(
            self.params, self.cfg, b, scfg.max_len,
            **({"frames": jnp.asarray(frames)} if frames is not None else {}))

        # one slot per request; FIFO admission puts prompt r into slot r,
        # matching batch row r of the decode state. Retirement (EOS/budget)
        # is per-slot; the lockstep cache layout pins admission to the
        # prefill, so the pool drains without re-fill (DESIGN.md §5.2).
        pool: SlotPool[int, None] = SlotPool(b)
        for r in range(b):
            pool.submit(r)
        pool.admit()

        # left-aligned prompt matrix; in-vocab filler beyond each prompt
        # (see __init__: raw tok.PAD may be out-of-vocab under smoke configs)
        mat = np.full((b, max_prompt), self._fill, np.int32)
        for r, p in enumerate(prompts):
            mat[r, :len(p)] = p
        key = jax.random.PRNGKey(scfg.seed)
        outs: List[List[int]] = [[] for _ in range(b)]
        logits = None
        self.n_steps = 0
        for t in range(max_prompt):
            logits, state = self._step(self.params, state, mat[:, t:t + 1])
            self.n_steps += 1
        # first generated token comes from the final prompt position
        for i in range(max_new_tokens):
            lg = np.asarray(logits, np.float32)
            if scfg.temperature > 0:
                key, k2 = jax.random.split(key)
                nxt = np.asarray(jax.random.categorical(
                    k2, jnp.asarray(lg) / scfg.temperature, axis=-1))
            else:
                nxt = lg.argmax(-1)
            for r, _ in list(pool.live()):
                outs[r].append(int(nxt[r]))
                if nxt[r] == scfg.eos_id or len(outs[r]) >= max_new_tokens:
                    pool.retire(r)
            if pool.n_live == 0:
                break
            logits, state = self._step(self.params, state,
                                       nxt.astype(np.int32)[:, None])
            self.n_steps += 1
        return [np.array(o, np.int32) for o in outs]
