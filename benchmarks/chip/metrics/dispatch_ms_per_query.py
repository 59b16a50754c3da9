"""Device plane (serve/engine.py, span nks.device.dispatch): ms per served
query spent sending the device tier's packed groups to the device and
calling the anchor-star program, the readback left out: the sum of
PipelineStats.t_dispatch_s over the window's batches. None for a program
without t_readback_s, whose t_dispatch_s still holds the readback. Moves
queries_per_s."""


def read(w):
    if not w.batch_stats or any(getattr(s, "t_readback_s", None) is None
                                for s in w.batch_stats):
        return None
    return w.per_query(1e3 * sum(s.t_dispatch_s for s in w.batch_stats))
