"""lm_prefill_ms: device time of one prefill chunk of the LM engine: per
traced step with chunks, its device busy time less the decode step's
(``lm_decode_ms``) over its chunks, averaged (``systems/_lm_spans.py``).
None without ``lm.*`` spans."""

from systems import _lm_spans


def read(run):
    return _lm_spans.prefill_ms(run.trace)
