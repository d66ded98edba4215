"""The LM serve path (``serve/engine.Engine``) as a benchmark system, bound
to the contract of ``systems/__init__.py``.

A configuration (``configs/deepseek-v2-lite-ep8.json``) holds the model's
published ``config.json`` keys, the share of it this chip holds and the
serving settings. A request is a document-QA prompt: one of the pool's
documents followed by one of its questions, ``prompt_tokens`` in all, and it
asks for ``length`` units of ``block_tokens`` generated tokens: the traffic
generator (``harness/traffic.py``) draws 2-16 units, so the unit is a block
of tokens and not a token. ``offset // SCENE_FRAMES`` picks the document, so
each document is asked by up to ``SCENE_FRAMES`` questions. Every token is
sampled greedily and EOS is ignored, so the lengths are the traffic's.

The check teacher-forces each window and drain request's prompt and
generated tokens through the float32 reference (``_lm_reference.py``) and
reads, per generated token and in units of the standard deviation of the
reference's row of logits:

  logit_err_max   |program's logit of its token - reference's logit of it|
  lse_err_max     |program's log-sum-exp - reference's|
  argmax_gap_max  reference's largest logit - its logit of the program's
                  token (0 when the program took the reference's argmax)
  logit_err_mean  the mean of the first over every generated token

and ``lost_requests`` (never retired, or retired short). The bf16
program's largest errors come from router near-ties, where bf16 and
float32 pick another of the top-k experts for a token.

The seed's weights are the benchmark's own (``_lm_reference.init_weights``),
placed in the program's parameter tree: only that layout is shared with the
program.
"""

from __future__ import annotations

import collections
from typing import Dict

import jax
import ml_dtypes
import numpy as np

from harness import traffic
from systems import _lm_reference as lm_reference


def model_config(config: Dict):
    """The program's ``ModelConfig`` for the configuration's keys."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, \
        YarnConfig

    c = config
    y = c.get("rope_scaling")
    yarn = None if not y else YarnConfig(
        factor=float(y["factor"]),
        original_max_position=int(y["original_max_position_embeddings"]),
        beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
        mscale=float(y["mscale"]), mscale_all_dim=float(y["mscale_all_dim"]))
    return ModelConfig(
        name=c["name"], family="moe", n_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        d_ff=int(c["intermediate_size"]), vocab_size=int(c["vocab_size"]),
        head_dim=int(c["v_head_dim"]), rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        n_dense_layers=int(c["first_k_dense_replace"]),
        moe=MoEConfig(
            n_experts=int(c["n_routed_experts_published"]),
            top_k=int(c["num_experts_per_tok"]),
            d_expert=int(c["moe_intermediate_size"]),
            n_shared=int(c["n_shared_experts"]),
            norm_topk=bool(c["norm_topk_prob"]),
            held=(int(c["e_lo"]), int(c["n_routed_experts"]))),
        mla=MLAConfig(kv_lora_rank=int(c["kv_lora_rank"]),
                      d_nope=int(c["qk_nope_head_dim"]),
                      d_rope=int(c["qk_rope_head_dim"]),
                      d_v=int(c["v_head_dim"]), yarn=yarn),
        dtype=c["dtype"], remat="none")


def pool(config: Dict, seed: int) -> Dict:
    """The seed's documents (one per ``SCENE_FRAMES`` offsets) and
    questions (one per offset), token ids uniform over the vocabulary."""
    rng = traffic.seed_rng(seed, 1)
    q = int(config["question_tokens"])
    n_docs = traffic.POOL_VOLLEYS // traffic.SCENE_FRAMES
    v = int(config["vocab_size"])
    return {"docs": rng.integers(0, v, (n_docs, int(config["prompt_tokens"])
                                        - q), dtype=np.int32),
            "questions": rng.integers(0, v, (traffic.POOL_VOLLEYS, q),
                                      dtype=np.int32),
            "block": int(config["block_tokens"])}


def payload(pool: Dict, offset: int, length: int):
    """Document ``offset // SCENE_FRAMES`` and question ``offset``; ``length``
    blocks of tokens to generate."""
    from repro.serve.engine import LMRequest

    return LMRequest(
        prompt=np.concatenate([pool["docs"][offset // traffic.SCENE_FRAMES],
                               pool["questions"][offset]]),
        max_new_tokens=length * pool["block"])


def init_params(key, config: Dict):
    """The seed's weights (``_lm_reference.init_weights``), made on the
    device."""
    return jax.jit(lambda k: lm_reference.init_weights(k, config))(key)


class _Handle:
    """A request's handle in the benchmark's units: ``outputs`` holds one
    (block, 3) array per block of generated tokens completed."""

    def __init__(self, req, block: int):
        self.req, self.req_id, self._block = req, req.req_id, block
        self.outputs = []

    def sync(self) -> int:
        """Add the blocks completed since the last call; returns their
        count."""
        b, k = self._block, len(self.outputs)
        n = len(self.req.tokens) // b - k
        if n > 0:
            out = self.req.result()
            self.outputs += [out[i * b:(i + 1) * b] for i in range(k, k + n)]
        return max(n, 0)

    def result(self) -> np.ndarray:
        return self.req.result()


class Program:
    """``serve/engine.Engine`` under the contract of ``systems/__init__``:
    its units are blocks of ``block_tokens`` generated tokens."""

    n_snapshots = 0

    def __init__(self, params, mc, config: Dict):
        from repro.serve.engine import Engine, ServeConfig

        self.eng = Engine(params, mc, ServeConfig(
            max_len=int(config["max_len"]), n_slots=int(config["n_slots"]),
            prefill_tokens_per_step=int(config["prefill_tokens_per_step"]),
            ignore_eos=bool(config["ignore_eos"])))
        self._block = int(config["block_tokens"])
        self._live: Dict[int, _Handle] = {}
        self.n_volleys = 0

    def submit(self, req) -> _Handle:
        h = _Handle(self.eng.submit(req), self._block)
        self._live[h.req_id] = h
        return h

    def step(self):
        retired = self.eng.step()
        for h in self._live.values():
            self.n_volleys += h.sync()
        return [self._live.pop(r.req_id) for r in retired]

    @property
    def has_work(self) -> bool:
        return self.eng.has_work

    def close(self) -> None:
        """Nothing runs in the background."""

    def final_state(self) -> None:
        """Nothing is learned: the weights are fixed."""
        return None


def engine(params, config: Dict, cell: str):
    """``serve/engine.Engine`` built for the cell."""
    return Program(params, model_config(config), config)


def control_engine(params, config: Dict):
    """The program with its weights rounded through float8_e4m3fn, one
    precision below the bf16 the configuration states (norm scales are 1,
    which float8 holds exactly). The rounding is numpy's (``ml_dtypes``, to
    nearest even) on the host, where no compiler may fold the round trip
    away; each leaf's device copy is freed before its rounded copy is
    placed, so the chip never holds two sets of weights."""
    def through_fp8(a):
        host = np.asarray(jax.device_get(a))
        a.delete()
        return jax.device_put(host.astype(ml_dtypes.float8_e4m3fn)
                              .astype(host.dtype))

    return Program(jax.tree.map(through_fp8, params), model_config(config),
                   config)


def stateful(config: Dict) -> bool:
    return False


def _blocks(n: int, most: int) -> int:
    """Length of the blocks that cover ``n`` positions in as few blocks of
    at most ``most`` as can, rounded up to a multiple of 8."""
    k = -(-n // most)
    return -(-(-(-n // k)) // 8) * 8


def compare(config: Dict, pool: Dict, params_host, requests, served,
            final_state) -> Dict[str, float]:
    """The numbers compared (module docstring)."""
    block = int(config["block_tokens"])
    lost = sum(1 for r in requests
               if r.out is None or len(r.out) != r.length * block)
    numbers = {"lost_requests": float(lost)}
    checked = [r for r in requests if not r.warmup and r.out is not None
               and len(r.out) == r.length * block]
    ref = lm_reference.Reference(params_host, config)
    doc_len = pool["docs"].shape[1]
    q_len = pool["questions"].shape[1]
    most = int(config["reference_block"])
    doc_block = _blocks(doc_len, most)
    tail_block = _blocks(q_len + int(config["max_len"]) - int(
        config["prompt_tokens"]), most)
    by_doc = collections.defaultdict(list)
    for r in checked:
        by_doc[r.offset // traffic.SCENE_FRAMES].append(r)
    errs = []
    for doc, reqs in sorted(by_doc.items()):
        kv = ref.empty()
        tokens = pool["docs"][doc]
        for s in range(0, doc_len, doc_block):
            chunk = np.zeros((doc_block,), np.int32)
            n = min(doc_block, doc_len - s)
            chunk[:n] = tokens[s:s + n]
            kv, _ = ref.run(kv, chunk, s, n, chunk)
        for r in reqs:
            out = np.asarray(r.out)
            ids = out[:, 0].astype(np.int32)
            # the question, then every generated token but the last; the
            # row of the question's last token predicts the first
            seq = np.concatenate([pool["questions"][r.offset], ids[:-1]])
            tgt = np.concatenate([np.zeros(q_len - 1, np.int32), ids])
            stats, kv_r = [], kv
            for s in range(0, seq.size, tail_block):
                n = min(tail_block, seq.size - s)
                chunk = np.zeros((tail_block,), np.int32)
                chunk[:n] = seq[s:s + n]
                t = np.zeros((tail_block,), np.int32)
                t[:n] = tgt[s:s + n]
                kv_r, st = ref.run(kv_r, chunk, doc_len + s, n, t)
                stats.append(np.asarray(st)[:n])
            st = np.concatenate(stats)[q_len - 1:]
            logit, lse, top, std = st.T
            errs.append(np.stack([np.abs(out[:, 1] - logit) / std,
                                  np.abs(out[:, 2] - lse) / std,
                                  (top - logit) / std], -1))
    errs = np.concatenate(errs) if errs else np.zeros((1, 3))
    worst = np.max(errs, axis=0)
    numbers.update(logit_err_max=float(worst[0]), lse_err_max=float(worst[1]),
                   argmax_gap_max=float(worst[2]),
                   logit_err_mean=float(np.mean(errs[:, 0])))
    return numbers
