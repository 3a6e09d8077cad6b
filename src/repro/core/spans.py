"""Host spans of the served path, on the profiler's clock.

``with span("xf.pack", acc):`` times its body into ``acc["pack_s"]``
(``time.perf_counter`` seconds, summed) and opens a
:class:`jax.profiler.TraceAnnotation` of the same name carrying
``batch=<dispatch sequence number>``.  A running profiler writes the
span into its own ``.xplane.pb`` on ``/host:CPU``, the file and clock of
the device's ``XLA Ops`` line; without one the annotation costs only the
check whether tracing is on.  The counters are kept either way.

The batch id ties one batch's spans together across the serve loop's
threads: the thread that works on a batch sets :data:`BATCH`, and a span
opened without an explicit ``batch=`` takes it from there.
"""
from __future__ import annotations

import contextvars
import time

from jax.profiler import TraceAnnotation

#: the dispatch sequence number of the batch this thread works on
#: (``-1`` outside the serve loop)
BATCH: contextvars.ContextVar[int] = contextvars.ContextVar("xf_batch",
                                                            default=-1)


class span:
    """Time a block into ``acc["<name minus 'xf.'>_s"]`` and annotate it
    in the profiler's trace."""

    __slots__ = ("_acc", "_key", "_ann", "_t0")

    def __init__(self, name: str, acc: dict, batch: int | None = None):
        self._acc = acc
        self._key = name.removeprefix("xf.") + "_s"
        self._ann = TraceAnnotation(
            name, batch=BATCH.get() if batch is None else batch)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._acc[self._key] = self._acc.get(self._key, 0.0) + dt
        self._ann.__exit__(*exc)
