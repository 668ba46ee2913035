"""Programs compiled, or loaded from the persistent cache, inside the
window: the program buckets no shapes, so each new batch size, chunk
length or page-table width compiles."""


def read(rec):
    return rec.compiles
