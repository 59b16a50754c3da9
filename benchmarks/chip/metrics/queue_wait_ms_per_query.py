"""Runtime (serve/runtime.py): ms per served query from submit until the
runtime's worker took the request into a batch, the 2 ms coalescing wait
included: RuntimeStats.t_queue_wait_s over the window. None where the
program has no such counter. Moves queries_per_s."""


def read(w):
    seconds = w.runtime.get("t_queue_wait_s")
    if seconds is None or not w.batch_stats:
        return None
    return w.per_query(1e3 * seconds)
