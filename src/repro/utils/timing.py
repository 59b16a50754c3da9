"""Spans: stage timers that also land in the profiler's trace.

``with span("nks.engine.plan", stats, "t_plan_s"):`` times the block on the
host clock and adds the seconds to ``stats.t_plan_s`` (``field`` may name
several fields, each of which gets the same seconds). It also opens a
``jax.profiler.TraceAnnotation`` of that name, with ``meta`` as its
metadata, so that while a profiler runs the span sits in the trace on the
device's clock. The annotation is opened only when JAX is already imported
and a trace is recording: without JAX no profiler can run, and the numpy
control plane stays importable without it.
"""
from __future__ import annotations

import sys
import time


class span:
    """Context manager: time a block into ``stats.<field>`` and annotate it
    in the profiler's trace under ``name``."""

    __slots__ = ("name", "stats", "field", "meta", "_t0", "_ann")

    def __init__(self, name: str, stats=None,
                 field: str | tuple[str, ...] | None = None, **meta):
        self.name = name
        self.stats = stats
        self.field = (field,) if isinstance(field, str) else (field or ())
        self.meta = meta

    def __enter__(self) -> "span":
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        self._ann = None
        # Built only while a trace records: with no profiler running, an
        # annotation object costs ten times the check.
        if profiler is not None and profiler.TraceAnnotation.is_enabled():
            self._ann = profiler.TraceAnnotation(self.name, **self.meta)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        seconds = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.stats is not None:
            for f in self.field:
                setattr(self.stats, f, getattr(self.stats, f) + seconds)
        return False
