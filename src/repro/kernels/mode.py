"""How Pallas kernels run on the current platform — decided here, once.

On a TPU every kernel compiles with Mosaic.  On the CPU (tests, and the
tiny-size rehearsal of a chip run) the same kernels run in TPU-interpret
mode, which emulates DMAs and semaphores.  Any other platform is an
error: nothing falls back.

Callers never pass an interpret flag; ``pallas_call(...,
interpret=mode.interpret())`` is the only spelling.  A test that compiles
for a described TPU topology from a CPU process patches :func:`platform`.
"""
from __future__ import annotations

from typing import Any

import jax

__all__ = ["platform", "on_tpu", "interpret", "decode_attn_impl"]


def platform() -> str:
    """The platform kernels are built for (``jax.default_backend()``)."""
    return jax.default_backend()


def on_tpu() -> bool:
    return platform() == "tpu"


def interpret() -> Any:
    """Value for ``pallas_call(interpret=...)``: compiled on a TPU,
    TPU-interpret mode on the CPU."""
    p = platform()
    if p == "tpu":
        return False
    if p == "cpu":
        from jax.experimental.pallas import tpu as pltpu

        return pltpu.InterpretParams()
    raise RuntimeError(f"no Pallas TPU kernel path for platform {p!r}")


def decode_attn_impl(attn_impl: Any = None) -> str:
    """Paged decode attention implementation: an explicit ``"pallas"``
    or ``"chunked"`` (the jnp oracle) wins; otherwise the compiled kernel
    on a TPU and the oracle elsewhere, where the kernel would only run
    interpreted."""
    if attn_impl is None:
        return "pallas" if on_tpu() else "ref"
    return "pallas" if attn_impl == "pallas" else "ref"
