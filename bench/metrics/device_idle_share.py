"""Device: percent of the traced window in which no operation ran on the
device (1 − union of the trace's op intervals / window), averaged over the
chips used."""


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    return 100.0 * tr.idle_share
