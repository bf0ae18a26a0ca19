"""Counts the compilations JAX makes while a window is open: every
backend compile, and every program read back from the persistent cache
(a program the set-up should have warmed)."""
from __future__ import annotations

import jax

EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)

_open = []  # the counters now open; one listener serves them all
_listening = False


def _listener(name, *_args, **_kw):
    if name in EVENTS:
        for c in _open:
            c.count += 1


class Counter:
    def __init__(self):
        self.count = 0

    def __enter__(self):
        global _listening
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_listener)
            _listening = True
        _open.append(self)
        return self

    def __exit__(self, *exc):
        _open.remove(self)
