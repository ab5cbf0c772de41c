"""Named host spans at the layer boundaries of a batch re-plan.

``span(name, **meta)`` opens a ``jax.profiler.TraceAnnotation`` named
``name``, so the span lands in the profiler's own trace on the same clock
as the device operations.  JAX is taken from ``sys.modules``: a profiler
can only be running if JAX is already imported, and without it the call
returns one shared no-op context, which keeps ``repro.core`` importable
with NumPy alone.  With no profiler running an annotation costs about a
microsecond.  A span only marks time: nothing reads it back, and it
carries no behaviour.

Each span nests inside its caller on the calling thread, and the nesting
is the "caused by" link.  Metadata names the work: ``round`` on rounds,
``jid`` on steps, ``rows`` on device calls.

    repro.search.round              one bisection round (core.api)
      repro.columnar.place          one job's step over every live branch
        repro.placement.pick_orders pool stats + pick rankings, operand prep
          repro.placement.launch    jit call on the one operand buffer
          repro.placement.fetch     np.asarray of the one result buffer
          repro.placement.recheck   float64 recompute of screened rows
          repro.placement.rank      NumPy FA-FFP / LBSGF stable rankings
        repro.columnar.score        rho-hat probe scoring
          repro.placement.score_probes  (heterogeneous clusters)
            repro.placement.launch / fetch / recheck
        repro.columnar.apply        commit, dedup, row clones
"""
from __future__ import annotations

import contextlib
import functools
import sys

SEARCH_ROUND = "repro.search.round"
COLUMNAR_PLACE = "repro.columnar.place"
COLUMNAR_SCORE = "repro.columnar.score"
COLUMNAR_APPLY = "repro.columnar.apply"
PICK_ORDERS = "repro.placement.pick_orders"
SCORE_PROBES = "repro.placement.score_probes"
LAUNCH = "repro.placement.launch"
FETCH = "repro.placement.fetch"
RECHECK = "repro.placement.recheck"
RANK = "repro.placement.rank"

#: Every span the program opens.
NAMES = (SEARCH_ROUND, COLUMNAR_PLACE, COLUMNAR_SCORE, COLUMNAR_APPLY,
         PICK_ORDERS, SCORE_PROBES, LAUNCH, FETCH, RECHECK, RANK)

_NOOP = contextlib.nullcontext()


def span(name: str, **meta):
    """A host span ``name`` (one of :data:`NAMES`) in the profiler's
    trace, or a no-op context when JAX is not imported."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NOOP
    return jax.profiler.TraceAnnotation(name, **meta)


def spanned(name: str):
    """Decorate a function so that each call runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return run
    return wrap
