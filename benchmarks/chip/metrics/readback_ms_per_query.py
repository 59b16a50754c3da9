"""Device plane (serve/engine.py, span nks.device.readback): ms per served
query spent waiting on the device and copying the k selected sets back to
the host: the sum of PipelineStats.t_readback_s over the window's batches.
None where the program has no such field. Moves queries_per_s."""


def read(w):
    seconds = [getattr(s, "t_readback_s", None) for s in w.batch_stats]
    if not seconds or None in seconds:
        return None
    return w.per_query(1e3 * sum(seconds))
