"""Device plane (core/device_plane.py): ms per served query spent on the
host packing the device tier's keyword groups into padded tiles, the sum of
PipelineStats.t_pack_s over the window's batches. Moves queries_per_s."""


def read(w):
    if not w.batch_stats:
        return None
    return w.per_query(1e3 * sum(s.t_pack_s for s in w.batch_stats))
