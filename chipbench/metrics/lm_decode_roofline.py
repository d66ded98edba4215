"""lm_decode_roofline: the LM decode step's share of its roofline, in %: the
least time of the traced slice's decode steps, averaged, over their measured
device time (``lm_decode_ms``).

A step's least time is the larger of its operations over the bf16 peak and
its bytes over the memory bandwidth (``systems/_lm_work.decode_work``): the
weights read once, and the latent cache up to each decoding slot's position.
The positions come from the requests: a decoding slot decodes one token in
every step until it retires, and EOS is ignored, so a request of N tokens
after a P-token prompt decoded in the N - 1 steps up to the one that retired
it, attending over P + N - 1 - j positions j steps before that one."""

import collections

from harness import spec
from systems import _lm_work


def _keys_per_step(run):
    c = run.config
    p, block = int(c["prompt_tokens"]), int(c["block_tokens"])
    index = {float(end): k for k, end in enumerate(run.steps[:, 1])}
    keys = collections.defaultdict(list)
    for r in run.requests:
        k = index.get(float(r.done))
        if k is None:
            continue
        n = r.length * block
        for j in range(n - 1):
            keys[k - j].append(p + n - 1 - j)
    return keys


def read(run):
    decode_ms = spec.load_reader("lm_decode_ms")(run)
    if decode_ms is None or not run.peak or run.config.get("system") != "lm":
        return None
    keys, traced = _keys_per_step(run), run.slice_mask()
    least = [_lm_work.least_seconds(*_lm_work.decode_work(run.config,
                                                          keys[k]), run.peak)
             for k in range(len(run.steps)) if traced[k] and keys.get(k)]
    if not least:
        return None
    return 100.0 * sum(least) / len(least) / (decode_ms / 1e3)
