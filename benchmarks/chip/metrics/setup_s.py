"""Set-up seconds: process start to window open (weights, engine, the fill
the traffic needs, warm-up and the programs it compiles or loads)."""


def read(rec):
    return rec.setup_s
