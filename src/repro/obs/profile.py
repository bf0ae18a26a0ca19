"""Kernel profiling by timed re-execution: the measurement side of the cost loop.

PR 9's tracer records host walls *around* initiation and sync — good
enough to fit α/β from end-to-end transfer spans, but blind to the one
constant the GAScore's hardware counters measure directly: γ, the
receiver-side epilogue per KiB, which overlaps the wire by design and
therefore never separates out of an end-to-end wall.  This module
closes that gap the way ACCL+'s engine counters do — time the epilogue
program *alone*, at several sizes, and hand its per-KiB slope to
:meth:`repro.core.sched.EngineCost.fit_from_trace` as
``epilogue_spans``.

Measurement discipline:

- **Interleaved timed re-execution.**  Run the target repeatedly
  under ``perf_counter`` with ``block_until_ready`` fencing each call,
  interleaving targets round-robin so machine-load drift lands on all
  of them equally, and keep the best-of-N (scheduler noise only ever
  adds time).
- **Honest labelling.**  Every sample is a host wall clock around the
  call, on any backend, and carries ``measured="wall"``; device time
  comes only from a profiler trace of the device, which this module
  does not read.
- **Never on the serving hot path.**  Profiling is an offline,
  explicit re-execution of a target — the ``obs_overhead`` gate
  (< 1.02x with tracing on, profiler idle) is unaffected by anything
  in this module.

Like the rest of ``repro.obs`` this module imports nothing from the
core/serving layers; servers and benches hand it plain callables.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

from repro.obs import trace as obs_trace

__all__ = [
    "DeviceProfiler",
    "measure",
]


def _block(x: Any) -> Any:
    """Fence a target's result: device work must retire before the
    timer stops.  Host-side results (numpy, floats) pass through."""
    try:
        import jax

        return jax.block_until_ready(x)
    except Exception:
        return x


def measure(
    fn: Callable[[], Any],
    *,
    iters: int = 8,
    warmup: int = 2,
) -> tuple:
    """Time ``fn()`` by re-execution: ``warmup`` unrecorded calls (JIT
    compile + cache warm), then ``iters`` timed calls, each fenced with
    ``block_until_ready``.  Returns ``(best_us, measured)`` where
    ``measured`` names the clock: always ``"wall"``, the host's."""
    for _ in range(max(warmup, 0)):
        _block(fn())
    best = None
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        _block(fn())
        dt = (time.perf_counter() - t0) * 1e6
        best = dt if best is None or dt < best else best
    return best, "wall"


class DeviceProfiler:
    """Records timed kernel/program samples as profile spans.

    Each :meth:`profile` call produces one record — a plain dict with
    ``name`` / ``dur_us`` / ``measured`` plus caller tags (``bytes=``
    makes it a valid fit point for
    :meth:`~repro.core.sched.EngineCost.fit_from_trace`) — kept on
    ``self.records`` and, when tracing is enabled, mirrored onto the
    active tracer as a ``cat="profile"`` instant so profiled kernels
    appear in the exported timeline next to the spans they explain.
    """

    def __init__(self, tracer: Optional[Any] = None):
        self._tracer = tracer
        self.records: List[Dict[str, Any]] = []

    def _emit(self, rec: Dict[str, Any]) -> None:
        self.records.append(rec)
        tr = self._tracer if self._tracer is not None else obs_trace.active()
        if tr.enabled:
            tr.instant(rec["name"], cat="profile",
                       **{k: v for k, v in rec.items() if k != "name"})

    # ---------------------------------------------------------------- #
    def profile(
        self,
        name: str,
        fn: Callable[[], Any],
        *,
        iters: int = 8,
        warmup: int = 2,
        **tags: Any,
    ) -> float:
        """Time one target; returns its best-of-N microseconds."""
        best_us, measured = measure(fn, iters=iters, warmup=warmup)
        self._emit({"name": name, "dur_us": round(best_us, 3),
                    "measured": measured, **tags})
        return best_us

    def profile_many(
        self,
        targets: Sequence[tuple],
        *,
        rounds: int = 6,
        warmup: int = 2,
    ) -> Dict[str, float]:
        """Interleaved timed re-execution of several targets.

        ``targets`` is a sequence of ``(name, fn)`` or
        ``(name, fn, tags)`` tuples.  Each round times every target
        once, round-robin, so load drift during the run biases none of
        them; per-target best-of-rounds is recorded.  Returns
        ``{name: best_us}``."""
        norm = [
            (t[0], t[1], t[2] if len(t) > 2 else {}) for t in targets
        ]
        for name, fn, _ in norm:
            for _ in range(max(warmup, 0)):
                _block(fn())
        best: Dict[str, float] = {}
        for _ in range(max(rounds, 1)):
            for name, fn, _ in norm:
                t0 = time.perf_counter()
                _block(fn())
                dt = (time.perf_counter() - t0) * 1e6
                if name not in best or dt < best[name]:
                    best[name] = dt
        for name, _, tags in norm:
            self._emit({"name": name, "dur_us": round(best[name], 3),
                        "measured": "wall", **tags})
        return best

    def profile_epilogue(
        self,
        make_fn: Callable[[int], Callable[[], Any]],
        sizes: Iterable[int],
        *,
        name: str = "epilogue",
        iters: int = 8,
        warmup: int = 2,
    ) -> List[Dict[str, Any]]:
        """Time the receiver-epilogue program alone at several payload
        sizes — the γ measurement.  ``make_fn(nbytes)`` must return a
        zero-arg callable executing the epilogue (the install/
        accumulate/store a receiver runs per landed segment) over a
        payload of ``nbytes``.  The returned records carry ``bytes``
        tags and feed ``EngineCost.fit_from_trace(...,
        epilogue_spans=...)`` / ``fit_gamma_from_trace`` directly."""
        out = []
        for nbytes in sizes:
            fn = make_fn(int(nbytes))
            best_us, measured = measure(fn, iters=iters, warmup=warmup)
            rec = {"name": name, "dur_us": round(best_us, 3),
                   "measured": measured, "bytes": int(nbytes)}
            self._emit(rec)
            out.append(rec)
        return out

    # ---------------------------------------------------------------- #
    def spans(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        """Recorded samples (optionally filtered by name) — dict-shaped
        fit points accepted by ``EngineCost._points``."""
        if name is None:
            return list(self.records)
        return [r for r in self.records if r["name"] == name]
