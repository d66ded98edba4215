"""Operations and bytes an LM configuration's steps need, from its shapes.

Counted from the logical shapes of the configuration file (HF keys, see
``configs/deepseek-v2-lite-ep8.json``), so the work is the same whatever
implements it. Weights and the latent cache count at the configuration's
``dtype`` (bf16: 2 bytes), the router at float32.

A decode step over ``rows`` decoding slots, slot i attending over ``keys_i``
positions (its own included):

  bytes = every weight read once (all held experts: with rows x top-k
          assignments spread over the experts nearly every held expert has
          a token), the embedding rows looked up, and per layer the latent
          and rotary keys read up to each slot's position and written for
          its new token
  flops = 2 x (multiply-adds): per token the projections (``w_ukv`` once:
          in the absorbed form, the key absorption into the query and the
          value up-projection), the expected expert work (top-k x held /
          routed experts, plus the shared experts) and the head; per key of
          the latent attention 2 x H x (R + d_rope) for the scores and
          2 x H x R for the weighted sum

A prefill chunk of ``n`` tokens at positions ``start`` onward runs the
attention expanded: per token the projections (``w_ukv`` once: the
up-projection of its own key and value), and per (query, visible key)
2 x H x (d_nope + d_rope) plus 2 x H x d_v; the head once, for the chunk's
last position.
"""

from __future__ import annotations

from typing import Dict, Sequence

#: bytes of the dtypes a configuration states
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def dims(c: Dict) -> Dict[str, int]:
    d = {k: int(c[k]) for k in (
        "hidden_size", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
        "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "n_routed_experts_published", "n_shared_experts",
        "num_experts_per_tok", "num_hidden_layers", "first_k_dense_replace",
        "vocab_size")}
    d["bytes"] = DTYPE_BYTES[c["dtype"]]
    return d


def _attn_params(d) -> int:
    h, dm, r = d["num_attention_heads"], d["hidden_size"], d["kv_lora_rank"]
    qk = d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
    return (dm * h * qk + dm * r + dm * d["qk_rope_head_dim"]
            + r * h * (d["qk_nope_head_dim"] + d["v_head_dim"])
            + h * d["v_head_dim"] * dm)


def _expert(d) -> int:
    return 3 * d["hidden_size"] * d["moe_intermediate_size"]


def _layers(d):
    nd = d["first_k_dense_replace"]
    return nd, d["num_hidden_layers"] - nd


def weight_bytes(c: Dict) -> int:
    """Bytes of every weight a decode step reads (not the embedding)."""
    d = dims(c)
    nd, nm = _layers(d)
    dm = d["hidden_size"]
    moe = ((d["n_routed_experts"] + d["n_shared_experts"]) * _expert(d)
           * d["bytes"] + dm * d["n_routed_experts_published"] * 4)
    per = _attn_params(d) * d["bytes"]
    return (d["num_hidden_layers"] * per + nd * 3 * dm
            * d["intermediate_size"] * d["bytes"] + nm * moe
            + dm * d["vocab_size"] * d["bytes"])


def _token_flops(d) -> float:
    """Multiply-adds x 2 per token outside attention's key loop."""
    nd, nm = _layers(d)
    dm = d["hidden_size"]
    experts = (d["num_experts_per_tok"] * d["n_routed_experts"]
               / d["n_routed_experts_published"] + d["n_shared_experts"])
    moe = dm * d["n_routed_experts_published"] + experts * _expert(d)
    return 2.0 * (d["num_hidden_layers"] * _attn_params(d)
                  + nd * 3 * dm * d["intermediate_size"] + nm * moe)


def decode_work(c: Dict, keys: Sequence[int]):
    """(flops, bytes) of one decode step whose decoding slots attend over
    ``keys`` positions each."""
    d = dims(c)
    rows, n_keys = len(keys), float(sum(keys))
    h, r, dr = d["num_attention_heads"], d["kv_lora_rank"], \
        d["qk_rope_head_dim"]
    L = d["num_hidden_layers"]
    flops = rows * (_token_flops(d)
                    + 2.0 * d["hidden_size"] * d["vocab_size"]) \
        + L * n_keys * 2.0 * h * (2 * r + dr)
    nbytes = (weight_bytes(c) + rows * d["hidden_size"] * d["bytes"]
              + L * (n_keys + rows) * (r + dr) * d["bytes"])
    return flops, nbytes


def prefill_flops(c: Dict, start: int, n: int) -> float:
    """Operations of one prefill chunk: ``n`` tokens at positions
    ``start, ..., start + n - 1`` (causal: position p sees p + 1 keys)."""
    d = dims(c)
    h = d["num_attention_heads"]
    per_key = 2.0 * h * (d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
                         + d["v_head_dim"])
    keys = n * start + n * (n + 1) / 2
    return (n * _token_flops(d) + d["num_hidden_layers"] * keys * per_key
            + 2.0 * d["hidden_size"] * d["vocab_size"])


def least_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The larger of the operations over the bf16 peak and the bytes over
    the memory bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
