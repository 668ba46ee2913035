"""Milliseconds per ``PagedDecoder.decode_step`` call, over every call of
the window (spans that wait for the logits)."""


def read(rec):
    if rec.spans is None or not rec.spans.calls["decode_step"]:
        return None
    return (1000.0 * rec.spans.seconds["decode_step"]
            / rec.spans.calls["decode_step"])
