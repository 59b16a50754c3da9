"""Kernels (kernels/pairwise_l2.py, and the device tier's anchor-star
program): device ms per served query inside the serving kernels' jitted
programs, from the trace's "XLA Modules" events. Moves queries_per_s."""

PROGRAMS = ("jit__join_batched_masked", "jit__join_batched_counts",
            "jit_nks_anchor_topk")


def read(w):
    if w.trace is None:
        return None
    seconds = sum(v for k, v in w.trace.module_seconds.items()
                  if k in PROGRAMS)
    return w.per_query(1e3 * seconds) if seconds > 0 else None
