"""The whole step's share of the chip's peak: model operations of every
token processed in the window (prefill chunks without logits, decode
tokens with them, attention over their contexts) over the window's
seconds as the profiler's trace holds them, times the peak FLOP/s."""


def read(rec):
    if rec.trace is None or not rec.model_flops:
        return None
    return 100.0 * rec.model_flops / (rec.trace.window_s * rec.peaks.flops)
