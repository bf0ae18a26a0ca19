"""Run context: mesh, axis roles, and implementation switches.

One immutable object threaded through model code so that the *same* model
definition runs:

- single-device (smoke tests, examples): ``mesh=None`` — no collectives;
- GSPMD production: mesh + axis names; parameter PartitionSpecs from the
  layer inits + boundary constraints drive the partitioner;
- paper-mode migrations: ``attn_impl``/``scan_impl``/``moe_backend`` flip
  individual hot spots between the verified software path ("ref"/"xla")
  and the hardware path ("pallas"/"gascore") with no model changes.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["RunCtx", "shard"]


@dataclasses.dataclass(frozen=True)
class RunCtx:
    mesh: Optional[jax.sharding.Mesh] = None
    dp: Tuple[str, ...] = ("data",)  # batch / FSDP axes (includes "pod")
    tp: str = "model"  # tensor/expert-parallel axis
    pp: Optional[str] = None  # pipeline axis ("pod") when enabled
    # implementation switches (software <-> hardware migration points)
    moe_mode: str = "auto"  # auto | ep_shardmap | local
    moe_backend: str = "xla"  # xla | gascore
    # chunked | pallas; None lets repro.kernels.mode pick the paged
    # decode kernel from the platform (train/prefill stay chunked)
    attn_impl: Optional[str] = None
    attn_chunk: int = 512
    scan_impl: str = "ref"  # ref | pallas
    remat: str = "full"  # none | full | dots
    # §Perf iteration A: constrain weights to their FSDP-gathered form at
    # the point of use, so the partitioner all-gathers the (small) weight
    # shard instead of all-reducing the (huge) activations.  False =
    # paper-faithful baseline (leave the partitioner to choose).
    fsdp_gather: bool = False
    # §Perf iteration D: shard the saved residual stream's sequence dim
    # over tp between blocks (sequence parallelism for stored activations).
    seq_shard_acts: bool = False

    @property
    def dp_size(self) -> int:
        if self.mesh is None:
            return 1
        return math.prod(self.mesh.shape[a] for a in self.dp)

    @property
    def tp_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.tp]

    def batch_spec(self) -> P:
        return P(self.dp)

    def hidden_spec(self) -> P:
        """(B, S, D) activations: batch over dp axes."""
        if self.seq_shard_acts:
            return P(self.dp, self.tp, None)
        return P(self.dp, None, None)


def use_weight(w, ctx: "RunCtx", spec: P):
    """FSDP unshard-at-use (iteration A): see RunCtx.fsdp_gather."""
    if not ctx.fsdp_gather or ctx is None or ctx.mesh is None:
        return w
    from repro.parallel.sharding import sanitize

    return shard(w, ctx, sanitize(spec, w.shape, ctx.mesh))


def shard(x: jax.Array, ctx: RunCtx, spec: P) -> jax.Array:
    """Sharding constraint that is a no-op without a mesh."""
    if ctx is None or ctx.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec)
    )
