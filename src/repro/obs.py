"""The program's trace spans, for ``jax.profiler.trace``.

Each span is a ``jax.profiler.TraceAnnotation``: the profiler keeps it in
memory and writes it into the same ``.xplane.pb`` as the device ops, on one
clock, when the trace stops.  With no trace running a span costs a fraction
of a microsecond, so the spans are always on.  Every name starts with
``repro.``; each lands on the host line of the thread that enters it.

=========================  ========  =========================================
span                       thread    contains
=========================  ========  =========================================
``repro.fit``              caller    all of ``StreamClusterer.fit``
``repro.clusterer.init``   caller    building a fresh state (``init_fn``)
``repro.pipeline.wait``    caller    waiting on the prefetch queue for a batch
``repro.pipeline.stage``   prefetch  producing one batch or megabatch: rechunk,
                                     pad, residency accounting, source reads
``repro.source.read``      prefetch  one pull from the source (read, decode)
``repro.dispatch``         caller    a backend call: the host-to-device copy
                                     and the asynchronous enqueue
``repro.finalize``         caller    all of ``StreamClusterer.finalize``
``repro.finalize.fetch``   caller    the state and labels copied to the host
``repro.labels``           caller    ``canonical_labels`` in ``.labels``
=========================  ========  =========================================

With ``prefetch=0`` there is no prefetch thread: ``repro.pipeline.stage``
and ``repro.source.read`` then nest in the caller's ``repro.fit``.
"""

from __future__ import annotations

import functools


def span(name: str):
    """A context manager that marks its block as the span ``name``."""
    # imported here so that the jax-free graph layer stays importable
    # without jax
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


def spanned(name: str):
    """Decorator: each call of the function is one span ``name``."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return inner

    return wrap
