"""Host settlement (serve/engine.py, span nks.engine.rescore): ms per
served query spent rescoring the device tier's k sets in float64 on the
host, ranking them and mapping their ids: the sum of
PipelineStats.t_rescore_s over the window's batches. None for a program
whose device tier does not time this stage, known by its lack of
t_readback_s. Moves queries_per_s."""


def read(w):
    if not w.batch_stats or any(getattr(s, "t_readback_s", None) is None
                                for s in w.batch_stats):
        return None
    return w.per_query(1e3 * sum(s.t_rescore_s for s in w.batch_stats))
