"""Batched LM serving engine: continuous batching with chunked prefill.

Requests come in through ``submit`` and are served by ``step``, one call at
a time, over a fixed pool of B slots: the batch rows of one compiled decode
step. Each slot owns its request's cache rows and its own position in them
(``attention.KVCache`` with vector ``pos``; ``transformer.per_slot_state``).
Every ``step()``:

  1. admits pending requests into free slots (FIFO, lowest free slot);
  2. prefills at most ``prefill_tokens_per_step`` prompt tokens, in chunks
     of the admitted requests in the order they were admitted: each chunk is
     one launch of ``transformer.prefill_chunk``, which writes the slot's
     cache rows for the chunk while attending over the chunks before it;
  3. runs one decode step over all B rows for every slot whose prompt is in
     its cache (absorbed latent attention for MLA models), the other rows
     masked so they neither write nor advance;
  4. fetches the step's new tokens, each with its logit and its row's
     log-sum-exp, in one transfer, and retires requests that are done.

Decoding is never starved by a queue of long prompts: prefill takes a fixed
share of each step (chunked prefill, as in Sarathi-Serve). The last sampled
token of every slot stays on the device between launches, so a step waits
on the device once, at its fetch. Because attention rows are independent
and the expert layer drops nothing, a request's tokens do not depend on
what else is served: continuous batching changes throughput, never outputs
(greedy; pinned by tests/test_serve_lm.py). On a TPU this holds between
slot counts of 2 or more only: a one-row decode matmul rounds differently
(DESIGN.md §5.2).

``serve`` and ``generate`` are loops over ``submit``/``step``. Families
whose decode state is not a positional KV cache (ssm / hybrid recurrences,
audio's per-request encoder output) are served by the static lockstep path.
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
    #: keep generating past EOS (serving benchmarks fix output lengths)
    ignore_eos: bool = False
    seed: int = 0
    #: slots of the continuous batch (``serve`` may ask for another count)
    n_slots: int = 8
    #: prompt tokens prefilled per step, at most; also the chunk length
    prefill_tokens_per_step: int = 512


@dataclasses.dataclass
class LMRequest:
    """One prompt: what ``Engine.submit`` takes, and the handle it returns.

    ``tokens``, ``logits`` and ``lse`` grow by one per generated token: its
    id, its logit and the log-sum-exp of its row."""

    prompt: np.ndarray              # (len,) int32 token ids
    max_new_tokens: int
    req_id: int = -1
    tokens: List[int] = dataclasses.field(default_factory=list)
    logits: List[float] = dataclasses.field(default_factory=list)
    lse: List[float] = dataclasses.field(default_factory=list)

    def result(self) -> np.ndarray:
        """(n, 3) float32: id, logit, log-sum-exp of each generated token."""
        return np.stack([self.tokens, self.logits, self.lse],
                        axis=-1).astype(np.float32).reshape(-1, 3)


@dataclasses.dataclass
class _Slot:
    fed: int = 0                    # prompt tokens in the cache
    planned: int = 0                # tokens whose launch has been issued


def _pick(logits: jax.Array, key, temperature: float) -> jax.Array:
    """(..., V) logits -> (..., 3) float32: sampled id, its logit, the
    row's log-sum-exp."""
    lg = logits.astype(jnp.float32)
    if temperature > 0:
        idx = jax.random.categorical(key, lg / temperature, axis=-1)
    else:
        idx = jnp.argmax(lg, axis=-1)
    logit = jnp.take_along_axis(lg, idx[..., None], axis=-1)[..., 0]
    return jnp.stack([idx.astype(jnp.float32), logit,
                      jax.nn.logsumexp(lg, axis=-1)], axis=-1)


class Engine:
    def __init__(self, params, cfg: ModelConfig, scfg: ServeConfig):
        self.params = params
        self.cfg = cfg
        self.scfg = scfg
        t = scfg.temperature

        def decode(p, st, last, active, key):
            logits, st = T.decode_rows(p, cfg, st, last[:, None], active)
            out = _pick(logits, key, t)
            return out, st, jnp.where(active, out[:, 0].astype(jnp.int32),
                                      last)

        def prefill(p, st, last, tokens, slot, start, n, key):
            logits, st = T.prefill_chunk(p, cfg, st, tokens, slot, start, n)
            out = _pick(logits, key, t)
            return out, st, last.at[slot].set(out[0].astype(jnp.int32))

        # the state (caches) and the last tokens are updated in place
        self._step = jax.jit(decode, donate_argnums=(1, 2))
        self._prefill = jax.jit(prefill, donate_argnums=(1, 2))
        self._static_step = jax.jit(
            lambda p, st, t: T.decode_step(p, cfg, st, t))
        # filler token for free slot rows and chunk padding: must be
        # in-vocab — smoke configs cap vocab below tok.PAD, and an
        # out-of-vocab lookup embeds as NaN (jnp.take fill), which would be
        # written into the cache. Stale NaN survives a slot reset (0 * NaN
        # in the probs @ V contraction over masked positions), so the row
        # would poison every request admitted after it. A finite filler
        # contributes exactly 0.
        self._fill = int(min(tok.PAD, cfg.vocab_size - 1))
        self._key = jax.random.PRNGKey(scfg.seed)
        self._chunk = min(scfg.prefill_tokens_per_step, scfg.max_len)
        self._next_id = 0
        self._step_id = 0
        self.pool: Optional[SlotPool[LMRequest, _Slot]] = None
        # counters: engine steps, prompt tokens prefilled, tokens decoded
        self.n_steps = 0
        self.n_prefill_tokens = 0
        self.n_decode_tokens = 0
        if self._per_slot_ok:
            self._build(scfg.n_slots)

    @property
    def _per_slot_ok(self) -> bool:
        """Families whose decode state re-fills per slot (KV caches)."""
        return self.cfg.family not in ("ssm", "hybrid", "audio")

    def _build(self, n_slots: int) -> None:
        """Fresh slots: ``n_slots`` rows of cache, all free."""
        if n_slots < 1:
            raise ValueError(f"need at least one slot, got {n_slots}")
        self._state = None          # free the old caches first
        self._state = jax.jit(lambda p: T.per_slot_state(
            T.init_serve_state(p, self.cfg, n_slots, self.scfg.max_len),
            n_slots))(self.params)
        self._last = jnp.full((n_slots,), self._fill, jnp.int32)
        self.pool = SlotPool(n_slots, on_admit=self._on_admit)

    @staticmethod
    def _on_admit(idx: int, entry) -> None:
        del idx
        entry.state = _Slot()

    # ------------------------------------------------------ front door
    def submit(self, req: LMRequest) -> LMRequest:
        """Queue a request; returns it as its handle (``req_id`` set)."""
        req.prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if req.prompt.size == 0:
            raise ValueError(f"empty prompt (request {self._next_id})")
        if req.prompt.size >= self.scfg.max_len:
            raise ValueError(f"prompt of {req.prompt.size} tokens does not "
                             f"fit max_len {self.scfg.max_len}")
        req.req_id = self._next_id
        self._next_id += 1
        self.pool.submit(req)
        return req

    @property
    def has_work(self) -> bool:
        return self.pool.has_work

    def step(self) -> List[LMRequest]:
        """Admit, prefill a chunk budget, decode once; returns the requests
        that retired."""
        self._step_id += 1
        self.n_steps += 1
        with jax.profiler.StepTraceAnnotation("lm.step",
                                              step_num=self._step_id):
            with jax.profiler.TraceAnnotation("lm.admit"):
                self.pool.admit()
                live = sorted(self.pool.live(), key=lambda e: e[1].seq)
            key = (jax.random.fold_in(self._key, self._step_id)
                   if self.scfg.temperature > 0 else self._key)
            launched = []           # (slot, device outputs) in token order
            budget = self.scfg.prefill_tokens_per_step
            for idx, entry in live:
                req, st = entry.item, entry.state
                n = min(self._chunk, req.prompt.size - st.fed, budget)
                if n <= 0:
                    continue
                chunk = np.full((self._chunk,), self._fill, np.int32)
                chunk[:n] = req.prompt[st.fed:st.fed + n]
                with jax.profiler.TraceAnnotation("lm.prefill"):
                    out, self._state, self._last = self._prefill(
                        self.params, self._state, self._last, chunk, idx,
                        st.fed, n, key)
                st.fed += n
                budget -= n
                self.n_prefill_tokens += n
                if st.fed == req.prompt.size:      # its first token
                    st.planned = 1
                    launched.append((idx, out))
                else:
                    launched.append((None, out))
            active = np.zeros((self.pool.n_slots,), bool)
            for idx, entry in live:
                req, st = entry.item, entry.state
                # the token to feed sits at position fed + planned - 1
                if (st.fed == req.prompt.size
                        and st.planned < req.max_new_tokens
                        and st.fed + st.planned - 1 < self.scfg.max_len):
                    active[idx] = True
                    st.planned += 1
            if active.any():
                with jax.profiler.TraceAnnotation("lm.decode"):
                    out, self._state, self._last = self._step(
                        self.params, self._state, self._last, active, key)
                self.n_decode_tokens += int(active.sum())
                launched.append((active, out))
            with jax.profiler.TraceAnnotation("lm.fetch"):
                got = jax.device_get([out for _, out in launched])
            with jax.profiler.TraceAnnotation("lm.retire"):
                return self._retire(launched, got)

    def _retire(self, launched, got) -> List[LMRequest]:
        done: Dict[int, LMRequest] = {}
        slots = dict(self.pool.live())
        for (which, _), out in zip(launched, got):
            if which is None:
                continue
            rows = ([(which, out)] if isinstance(which, int)
                    else [(i, out[i]) for i in np.flatnonzero(which)])
            for idx, row in rows:
                if idx in done:                    # retired by EOS
                    continue
                entry = slots[idx]
                if self._take(entry.item, row, entry.state):
                    done[idx] = entry.item
        for idx in done:
            self.pool.retire(idx)
        return list(done.values())

    def _take(self, req: LMRequest, row: np.ndarray, st: _Slot) -> bool:
        """Record one generated token; True when the request is done."""
        t_new = int(row[0])
        req.tokens.append(t_new)
        req.logits.append(float(row[1]))
        req.lse.append(float(row[2]))
        n = len(req.tokens)
        return (n >= req.max_new_tokens
                or st.fed + n - 1 >= self.scfg.max_len
                or (t_new == self.scfg.eos_id and not self.scfg.ignore_eos))

    # ------------------------------------------------------ batch APIs
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
        """Serve ``prompts`` through ``submit``/``step`` on ``n_slots`` rows;
        results in order.

        ``continuous=True`` re-fills freed slots from the pending queue
        mid-flight (the top of every step); ``continuous=False`` is the
        wave baseline — the next wave is submitted only when the pool has
        fully drained, so a batch's slowest request gates the next wave.
        Sampled tokens are identical either way under greedy decoding (per-
        row attention independence); only throughput differs.
        """
        b = len(prompts) if n_slots is None else int(n_slots)
        if self.pool is None or self.pool.has_work:
            raise RuntimeError("serve() needs an idle per-slot engine")
        if b != self.pool.n_slots:
            self._build(b)
        reqs = [LMRequest(prompt=p, max_new_tokens=max_new_tokens)
                for p in prompts]
        self.n_steps = 0
        waves = [reqs] if continuous else [reqs[i:i + b]
                                           for i in range(0, len(reqs), b)]
        for wave in waves:
            for r in wave:
                self.submit(r)
            while self.has_work:
                self.step()
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
            logits, state = self._static_step(self.params, state,
                                              mat[:, t:t + 1])
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
            logits, state = self._static_step(self.params, state,
                                              nxt.astype(np.int32)[:, None])
            self.n_steps += 1
        return [np.array(o, np.int32) for o in outs]
