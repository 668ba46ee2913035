"""95th percentile of every gap between consecutive output tokens of one
request, over all gaps whose two tokens both fall in the window."""

import numpy as np


def read(rec):
    gaps = []
    for lv in rec.live:
        t = [x for x in lv.token_times if rec.t_open <= x <= rec.t_close]
        gaps.extend(b - a for a, b in zip(t, t[1:]))
    return float(np.percentile(gaps, 95)) if gaps else None
