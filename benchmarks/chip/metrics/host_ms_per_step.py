"""Host milliseconds per engine step outside the decoder's calls: the
scheduler, the fabric view, page tables, sampling and the harness's own
bookkeeping. From the traced run's spans; the decoder's spans wait for
the device work they started."""


def read(rec):
    if rec.spans is None or not rec.steps:
        return None
    dec = rec.spans.seconds["forward_chunks"] + rec.spans.seconds["decode_step"]
    return 1000.0 * (rec.step_s - dec) / rec.steps
