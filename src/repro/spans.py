"""Named spans of the serving path, on the profiler's own clock.

``with span("route", rows=len(X)):`` marks a program stage as a
`jax.profiler.TraceAnnotation`.  While a profiler trace is being taken
(`jax.profiler.trace` or ``start_trace``/``stop_trace``) each span lands in
it as a host event on the calling thread's line, with its metadata, on the
same clock as the device's operations; a span nested inside another on the
same thread is its child.  With no trace running a span costs about a
microsecond and records nothing.  The profiler buffers the spans in memory
and writes them out when the trace stops.

The routed hop's spans (read from a trace by ``bench/harness/spans.py``):

* ``encode`` -- `repro.serving.encoder.embed_texts`; its children
  ``encode/tokenize`` (hashing and stacking the tokens),
  ``encode/dispatch`` (upload and encoder call, once per chunk) and
  ``encode/fetch`` (that chunk's copy to the host); its self time is the
  host normalisation.
* ``route`` -- `repro.serving.router_service.RouterService.route_fused`;
  its children ``route/dispatch`` (the call of the fused, tail-only or
  sharded program until it returns) and ``route/fetch`` (the one copy of
  the packed outputs to the host; ``buffers`` counts the arrays copied);
  its self time is the preparation and the uploads.

The serving engine's spans (`repro.serving.engine.ServingEngine`), each
with ``engine``, the model config's name:

* ``engine/prefill`` -- an admission of one or more requests: their
  slots' recurrent state zeroed and their prompts but the last tokens
  replayed together, until the caches are ready; ``rows`` is the prompts'
  tokens.
* ``engine/step`` -- one decode wave over the active slots (``rows``); its
  children ``engine/dispatch`` (the calls of the jitted decode step and of
  the greedy choice until they return) and ``engine/fetch`` (the copy of
  the chosen token ids to the host, which waits for the step).

Every span carries ``rows``: the batch size, or the prompts' tokens for
``engine/prefill``.  This module sits outside ``repro.serving`` because
`repro.core.routers.knn` uses it, and the serving package imports the
routers.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **meta) -> TraceAnnotation:
    """The annotation ``name`` with ``meta`` (such as ``rows``), to be used
    as a context manager around one stage."""
    return TraceAnnotation(name, **meta)
