"""Named host spans on the profiler's clock for the serving path.

Each span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``: it
lands in the same xplane as the device operations, on one clock, so a
trace reader can intersect a span with the device's busy intervals. Spans
are always on; with no profiler running one costs about a microsecond.
Counters live in ``ServingMetrics``, not here.

Attributes come back as the event's stats. Lists of query ids are joined
with ``;``: the profiler's metadata encoding splits values at commas.
"""
from __future__ import annotations

from typing import Iterable

import jax

PREFIX = "repro."

#: every span the serving path opens, as it appears in a trace
SPANS = (
    "repro.submit",        # GraphSession.submit [qid, algorithm]
    "repro.flush",         # GraphSession.flush [slots]
    "repro.dispatch",      # Dispatcher: one slot's launch [batch, algorithm,
    #                        width, n_real, qids]
    "repro.harvest",       # Dispatcher: one batch's results [batch, qids]
    "repro.harvest.wait",  # inside harvest: the host waits on the fixpoint
    "repro.parents",       # inside harvest: the batched parent transform
)


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """Context manager for the span ``repro.<name>`` with ``attrs``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **attrs)


def qid_list(qids: Iterable[int]) -> str:
    """The ``qids`` attribute: query ids joined with ``;``."""
    return ";".join(str(q) for q in qids)
