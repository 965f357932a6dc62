"""The serve engine's profiler spans.

The engine marks its scheduler and stage boundaries with
``jax.profiler`` annotations, so they land in the same trace as the
device's ``XLA Ops`` and ``XLA Modules`` lines, on the same clock: every
gap between the device's operations can be put down to the engine phase
the host was in.  With no profiler session active a span costs one
native call and records nothing.

These are the names the benchmark reads (``bench/metrics/
queue_wait_p95_ms.py`` reads ``engine.prefill``; ``bench/metrics/
host_ms_per_chunk.py`` reads ``engine.step``, ``engine.decode``,
``engine.wait`` and ``engine.idle``): a renamed span silences its
metric.  Every span opens on the thread that calls ``Engine.step`` and
they nest strictly (the benchmark's gap sweep relies on it).  They mark
scheduler and stage boundaries only, never a token, a layer or a slot.

Metadata (shown in TensorBoard and Perfetto, for operators):

=================  ====================================================
span               metadata
=================  ====================================================
``engine.step``    ``step_num`` (a step annotation, one per
                   ``Engine.step`` call that has work)
``engine.admit``   ``queued``: requests in the queue, arrived or not
``engine.prefill`` ``req``, ``tokens`` (the prompt's), ``rows``
                   (dispatched, padding included), ``queue_wait_ms``
                   (placement start minus due time); a wave dispatch
                   carries ``lanes``, ``tokens`` and ``rows`` instead
``engine.resume``  ``req``, ``rows`` (0 for a swap-in)
``engine.evict``   ``req``
``engine.decode``  ``active`` slots and ``steps`` at dispatch, ``rows``
                   (the active slot-steps it ran)
=================  ====================================================

``engine.upload`` (building a stage's device arguments),
``engine.wait`` (the host blocked on a stage's outputs),
``engine.readback`` (host copies of the outputs), ``engine.emit``
(appending and stamping tokens, finishing requests) and ``engine.idle``
(the sleep before a future arrival) carry none.
"""

from __future__ import annotations

from jax.profiler import StepTraceAnnotation as step_span  # noqa: F401
from jax.profiler import TraceAnnotation as span  # noqa: F401

STEP = "engine.step"
ADMIT = "engine.admit"
PREFILL = "engine.prefill"
RESUME = "engine.resume"
EVICT = "engine.evict"
DECODE = "engine.decode"
UPLOAD = "engine.upload"
WAIT = "engine.wait"
READBACK = "engine.readback"
EMIT = "engine.emit"
IDLE = "engine.idle"
