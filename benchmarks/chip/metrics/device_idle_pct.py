"""Device (TPU v5e): % of the traced window in which no operation ran on
the device (1 - union of device op intervals / window). Moves
queries_per_s."""


def read(w):
    return None if w.trace is None else w.trace.idle_pct
