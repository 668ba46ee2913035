"""Host spans around the calls into each layer of the program, taken from
the benchmark's own files: the program itself carries none yet.

``Spans.wrap`` shadows a bound method on one instance with a timed call,
for the life of that instance. Each call also writes a
``jax.profiler.TraceAnnotation`` named ``bench.<name>``, so the device
trace can put its idle gaps down to what the host was doing. With
``sync``, the span waits for what the call returned (or for ``sync_on()``)
to be ready before it ends, so that it measures the device work it
started and not only its dispatch.
"""

from __future__ import annotations

import collections
import contextlib
import time

import jax

from chipbench.tracereduce import SPAN_PREFIX


class Spans:
    def __init__(self):
        self.seconds: dict[str, float] = collections.defaultdict(float)
        self.calls: dict[str, int] = collections.defaultdict(int)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.seconds[name] += time.perf_counter() - t0
                self.calls[name] += 1

    def wrap(self, owner, attr: str, name: str, *, sync: bool = False,
             sync_on=None):
        """Time every call of ``owner.attr`` under ``name``."""
        fn = getattr(owner, attr)

        def timed(*args, **kwargs):
            with self.span(name):
                out = fn(*args, **kwargs)
                if sync:
                    jax.block_until_ready(sync_on() if sync_on else out)
                return out

        setattr(owner, attr, timed)


class OpsProxy:
    """The paged-attention ops module as the engine calls it, with every
    launch timed under its op name."""

    def __init__(self, ops, spans: Spans):
        self._ops, self._spans = ops, spans

    def __getattr__(self, name):
        op = getattr(self._ops, name)

        def launch(*args, **kwargs):
            with self._spans.span(name):
                return op(*args, **kwargs)
        return launch
