"""The whole step's share of the chip's peak in the MLA + MoE cells: the
model operations of every token processed in the window, as the
configuration's layout counts them (prefill chunks without logits, decode
tokens with them, latent attention over their contexts, the held experts
at their expectation), over the window's seconds as the profiler's trace
holds them, times the peak FLOP/s. The quotient of ``step_mfu``."""


def read(rec):
    if rec.trace is None or not rec.model_flops:
        return None
    return 100.0 * rec.model_flops / (rec.trace.window_s * rec.peaks.flops)
