"""Runtime (serve/runtime.py): ms per served query of the window spent
outside NKSEngine.query_batch: admission, the coalescing wait, the hand-offs
between the client's and the worker's threads, and the client's own loop.
Meaningful for a closed loop, where the window holds no idle wait for
arrivals. Moves queries_per_s."""


def read(w):
    if not w.batch_stats:
        return None
    return w.per_query(1e3 * (w.seconds - w.engine_seconds))
