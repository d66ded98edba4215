"""lm_decode_ms: device time of one decode step of the LM engine: the mean
device busy time of the traced steps that launched a decode step and no
prefill chunk (``systems/_lm_spans.py``). None without ``lm.*`` spans."""

from systems import _lm_spans


def read(run):
    return _lm_spans.decode_ms(run.trace)
