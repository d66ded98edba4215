"""lm_prefill_mfu: the LM prefill chunk's share of the chip's bf16 peak, in
%: the operations of a chunk (``systems/_lm_work.prefill_flops``, averaged
over the chunks that cover one ``prompt_tokens`` prompt at
``prefill_tokens_per_step`` a chunk) over its measured device time
(``lm_prefill_ms``) times the peak."""

from harness import spec
from systems import _lm_work


def read(run):
    prefill_ms = spec.load_reader("lm_prefill_ms")(run)
    if prefill_ms is None or not run.peak or run.config.get("system") != "lm":
        return None
    c = run.config
    p, step = int(c["prompt_tokens"]), int(c["prefill_tokens_per_step"])
    chunks = [_lm_work.prefill_flops(c, s, min(step, p - s))
              for s in range(0, p, step)]
    flops = sum(chunks) / len(chunks)
    return 100.0 * flops / (prefill_ms / 1e3 * run.peak["bf16_flops_per_s"])
