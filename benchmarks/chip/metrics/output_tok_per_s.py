"""Output tokens produced in the window over the window's seconds."""


def read(rec):
    n = sum(1 for lv in rec.live for t in lv.token_times
            if rec.t_open <= t <= rec.t_close)
    return n / rec.window_s
