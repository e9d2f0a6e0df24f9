"""Host spans: named intervals on the profiler's clock.

The program names its layers twice.  Device work carries ``mwis.*`` names
through ``jax.named_scope`` (op metadata only: no runtime cost, same
compiled code).  Host work that leaves the device idle or waits for it is
wrapped in :class:`span`, a ``jax.profiler.TraceAnnotation`` that a
profiler trace shows on the same clock as the device's ops and that costs
almost nothing when no trace is being taken.

Device scopes: ``mwis.rule.<family>``, ``mwis.rule.heavy``,
``mwis.aggregate``, ``mwis.exchange``, ``mwis.round.vote``, ``mwis.peel``.

Host spans: ``mwis.reduce.pack`` (holding ``mwis.reduce.plan`` and
``mwis.reduce.upload``); ``mwis.descent.stage``; ``mwis.serve.pack``,
``.stage``, ``.solve``, ``.fetch`` and ``.verify``.
"""

from __future__ import annotations

import time
from typing import Optional

import jax


class span:
    """Annotate host work as ``name`` in a profiler trace: ``with span(..)``
    around it, or :meth:`open` and :meth:`close` where the interval ends in
    another method than it starts.

    With ``rec``, the span's elapsed milliseconds are also added to
    ``rec[key]`` (a stage-time record), so a stage time and its trace span
    are one interval."""

    def __init__(self, name: str, rec: Optional[dict] = None,
                 key: Optional[str] = None):
        self.name, self.rec, self.key = name, rec, key
        self._ann = None
        self._t0 = 0.0

    def open(self) -> "span":
        self._t0 = time.perf_counter()
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        return self

    def close(self) -> None:
        """End the span; closing it again does nothing."""
        if self._ann is None:
            return
        self._ann.__exit__(None, None, None)
        self._ann = None
        if self.rec is not None:
            self.rec[self.key] = (self.rec.get(self.key, 0.0)
                                  + (time.perf_counter() - self._t0) * 1e3)

    def __enter__(self) -> "span":
        return self.open()

    def __exit__(self, *exc) -> None:
        self.close()
