"""Active Messages (GASNet Core API), in bulk-synchronous SPMD form.

A GASNet *Active Message* is a packet carrying a data payload, a destination
node, and the ID of a *handler function* that runs at the receiver when the
packet lands.  The paper's GAScore engine generates and consumes exactly
these packets in hardware.

TPUs have no receiver-side interrupts, so the handler-on-arrival semantics
are reproduced in the TPU-idiomatic way:

1. every node accumulates outgoing messages into a fixed-capacity
   :class:`AMBatch` (the "FIFO command queue" in front of the GAScore);
2. :func:`route` moves all batches simultaneously with a capacity-bounded
   all-to-all (the on-chip packet network) — this is a *static* SPMD
   schedule, the Pallas/XLA analogue of dynamic packet routing;
3. :func:`deliver` runs the registered handler of each landed message
   against the receiver's local state (the asynchronous handler call,
   now a fused receiver-side epilogue).

Message categories follow GASNet:

- **AMShort**  — handler args only, no payload.
- **AMMedium** — payload delivered to a bounded temporary buffer, handler
  decides placement.
- **AMLong**   — payload written at a caller-specified segment offset
  (``args[0]``); the built-in :func:`long_write_handler` reproduces the
  GAScore remote-DMA write.

**Request/reply** (GASNet Core rule: every AM is a *request* whose handler
may issue exactly one *reply* back to the requester, and reply handlers may
not reply again).  A handler registered with ``replies=True`` returns
``(state, AMReply)``; :func:`deliver_with_replies` collects the replies of
all landed requests into a second :class:`AMBatch` addressed at the sender
tokens, and :func:`request_reply` routes that batch in a second
:func:`route` hop and delivers it — the two-hop schedule is static, so the
whole round trip traces under ``jit`` + ``shard_map``.  Build replies with
:func:`reply_short` / :func:`reply_medium` (or :func:`no_reply` to decline).

Everything here is pure-functional and shape-static, so it traces/lowers
under ``jit`` + ``shard_map`` and is property-testable with hypothesis.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.obs import trace as obs_trace

__all__ = [
    "AMBatch",
    "AMReply",
    "HandlerTable",
    "empty_batch",
    "push",
    "build_send_buffer",
    "route",
    "deliver",
    "deliver_with_replies",
    "request_reply",
    "no_reply",
    "reply_short",
    "reply_medium",
    "long_write_handler",
]

MAX_ARGS = 4  # GASNet Core allows up to 16 handler args; 4 suffice here.


# --------------------------------------------------------------------------- #
# Handler registry
# --------------------------------------------------------------------------- #
class HandlerTable:
    """Ordered registry name -> (id, fn).

    Handler signature: ``fn(state, payload, args) -> state`` where ``state``
    is an arbitrary pytree (typically the node's local segment views),
    ``payload`` is a flat ``(payload_size,)`` vector and ``args`` a
    ``(MAX_ARGS,)`` int32 vector.  Handlers must be pure and return a pytree
    of identical structure (they are branches of one ``lax.switch``).

    A handler registered with ``replies=True`` is a GASNet *request*
    handler: it returns ``(state, AMReply)`` and its reply is routed back
    to the requester by :func:`request_reply`.  Reply handlers themselves
    must be plain (``replies=False``) — GASNet forbids replying to a reply,
    and :func:`request_reply` enforces this by discarding nested replies.
    """

    def __init__(self) -> None:
        self._names: List[str] = []
        self._fns: List[Callable] = []
        self._replies: List[bool] = []

    def register(self, name: str, fn: Callable, replies: bool = False) -> int:
        if name in self._names:
            raise ValueError(f"handler {name!r} already registered")
        self._names.append(name)
        self._fns.append(fn)
        self._replies.append(bool(replies))
        return len(self._names) - 1

    def handler(self, name: str, replies: bool = False) -> Callable:
        """Decorator form of :meth:`register`."""

        def deco(fn: Callable) -> Callable:
            self.register(name, fn, replies=replies)
            return fn

        return deco

    def id_of(self, name: str) -> int:
        return self._names.index(name)

    def replies_of(self, name: str) -> bool:
        return self._replies[self.id_of(name)]

    @property
    def fns(self) -> Tuple[Callable, ...]:
        return tuple(self._fns)

    @property
    def reply_flags(self) -> Tuple[bool, ...]:
        return tuple(self._replies)

    @property
    def has_replies(self) -> bool:
        return any(self._replies)

    def __len__(self) -> int:
        return len(self._names)


# --------------------------------------------------------------------------- #
# Message batches
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class AMBatch:
    """Fixed-capacity outgoing message queue of ONE node (local view).

    Attributes (capacity C, payload width Pw):
      dest:    (C,)  int32   destination node id per slot.
      handler: (C,)  int32   handler id per slot.
      args:    (C, MAX_ARGS) int32 handler arguments.
      payload: (C, Pw) payload rows (zero width for AMShort-only batches).
      valid:   (C,)  bool    slot occupancy.
      count:   ()    int32   number of occupied slots.
    """

    dest: jax.Array
    handler: jax.Array
    args: jax.Array
    payload: jax.Array
    valid: jax.Array
    count: jax.Array

    @property
    def capacity(self) -> int:
        return self.dest.shape[0]

    @property
    def payload_width(self) -> int:
        return self.payload.shape[1]


jax.tree_util.register_pytree_node(
    AMBatch,
    lambda b: ((b.dest, b.handler, b.args, b.payload, b.valid, b.count), None),
    lambda _, xs: AMBatch(*xs),
)


@dataclasses.dataclass
class AMReply:
    """Shape-static reply descriptor returned by a request handler.

    Attributes (payload width Pw of the delivering batch):
      send:    ()  bool    whether a reply is actually issued.
      handler: ()  int32   reply handler id (must be ``replies=False``).
      args:    (MAX_ARGS,) int32 reply handler arguments (AMReplyShort).
      payload: (Pw,)       reply payload (AMReplyMedium; zeros for Short).
    """

    send: jax.Array
    handler: jax.Array
    args: jax.Array
    payload: jax.Array


jax.tree_util.register_pytree_node(
    AMReply,
    lambda r: ((r.send, r.handler, r.args, r.payload), None),
    lambda _, xs: AMReply(*xs),
)


def _arg_vec(args: Sequence[Any]) -> jax.Array:
    vec = jnp.zeros((MAX_ARGS,), jnp.int32)
    for k, a in enumerate(args):
        vec = vec.at[k].set(jnp.asarray(a, jnp.int32))
    return vec


def no_reply(payload_width: int, dtype: Any = jnp.float32) -> AMReply:
    """The declined reply (the request handler stays one-way)."""
    return AMReply(
        send=jnp.zeros((), bool),
        handler=jnp.zeros((), jnp.int32),
        args=jnp.zeros((MAX_ARGS,), jnp.int32),
        payload=jnp.zeros((payload_width,), dtype),
    )


def reply_short(
    handler: int, args: Sequence[Any] = (), *, like: jax.Array
) -> AMReply:
    """AMReplyShort: handler id + args, no payload.  ``like`` is the request
    payload (or any ``(Pw,)`` vector of the batch dtype) fixing the reply
    payload shape — all ``lax.switch`` branches must agree on it."""
    return AMReply(
        send=jnp.ones((), bool),
        handler=jnp.asarray(handler, jnp.int32),
        args=_arg_vec(args),
        payload=jnp.zeros_like(like),
    )


def reply_medium(
    handler: int, payload: jax.Array, args: Sequence[Any] = ()
) -> AMReply:
    """AMReplyMedium: payload travels back to the requester."""
    return AMReply(
        send=jnp.ones((), bool),
        handler=jnp.asarray(handler, jnp.int32),
        args=_arg_vec(args),
        payload=payload,
    )


def empty_batch(capacity: int, payload_width: int, dtype: Any = jnp.float32) -> AMBatch:
    return AMBatch(
        dest=jnp.zeros((capacity,), jnp.int32),
        handler=jnp.zeros((capacity,), jnp.int32),
        args=jnp.zeros((capacity, MAX_ARGS), jnp.int32),
        payload=jnp.zeros((capacity, payload_width), dtype),
        valid=jnp.zeros((capacity,), bool),
        count=jnp.zeros((), jnp.int32),
    )


def push(
    batch: AMBatch,
    dest: jax.Array,
    handler: int,
    args: Sequence[Any] = (),
    payload: jax.Array | None = None,
    pred: jax.Array | bool | None = None,
) -> AMBatch:
    """Enqueue one message (functional).  Overflow beyond capacity is dropped
    silently here and surfaced by :func:`build_send_buffer` as a count —
    matching GASNet back-pressure semantics in a shape-static world.

    ``pred`` gates the enqueue (shape-static conditional send): under SPMD
    every rank traces the same ``push``, and a rank with nothing to say
    passes ``pred=False`` — the slot is simply not occupied."""
    i = jnp.minimum(batch.count, batch.capacity - 1)
    in_range = batch.count < batch.capacity
    if pred is not None:
        in_range = in_range & jnp.asarray(pred, bool)
    arg_vec = _arg_vec(args)
    if payload is None:
        payload = jnp.zeros((batch.payload_width,), batch.payload.dtype)
    payload = payload.astype(batch.payload.dtype).reshape(-1)
    if payload.shape[0] != batch.payload_width:
        raise ValueError(
            f"payload width {payload.shape[0]} != batch width {batch.payload_width}"
        )

    def write(b: AMBatch) -> AMBatch:
        return AMBatch(
            dest=b.dest.at[i].set(jnp.asarray(dest, jnp.int32)),
            handler=b.handler.at[i].set(jnp.asarray(handler, jnp.int32)),
            args=b.args.at[i].set(arg_vec),
            payload=b.payload.at[i].set(payload),
            valid=b.valid.at[i].set(True),
            count=b.count + 1,
        )

    return lax.cond(in_range, write, lambda b: b, batch)


# --------------------------------------------------------------------------- #
# Routing (the on-chip packet network)
# --------------------------------------------------------------------------- #
def build_send_buffer(
    batch: AMBatch, n_nodes: int, per_peer_capacity: int
) -> Tuple[AMBatch, jax.Array]:
    """Pack a node's outgoing queue into a dense (n_nodes * K)-slot buffer,
    slot ``d*K + r`` holding the r-th message addressed to node d.

    Returns the packed batch (capacity n_nodes*K, same widths) plus the
    number of messages dropped because more than K were addressed to one
    peer (the static-capacity analogue of network back-pressure).
    """
    K = per_peer_capacity
    dest = jnp.where(batch.valid, batch.dest, n_nodes)  # park invalid
    # rank of each message within its destination group (stable order)
    one_hot = (dest[:, None] == jnp.arange(n_nodes + 1)[None, :]).astype(jnp.int32)
    rank = jnp.cumsum(one_hot, axis=0) - one_hot  # exclusive prefix count
    rank = jnp.take_along_axis(rank, dest[:, None], axis=1)[:, 0]
    ok = batch.valid & (rank < K) & (dest < n_nodes)
    slot = jnp.where(ok, dest * K + rank, n_nodes * K)  # park dropped
    dropped = jnp.sum(batch.valid & ~ok)

    C = n_nodes * K

    def scatter(x: jax.Array, fill: Any) -> jax.Array:
        out = jnp.full((C + 1,) + x.shape[1:], fill, x.dtype)
        return out.at[slot].set(x)[:C]

    packed = AMBatch(
        dest=scatter(batch.dest, 0),
        handler=scatter(batch.handler, 0),
        args=scatter(batch.args, 0),
        payload=scatter(batch.payload, 0),
        valid=scatter(ok, False),
        count=jnp.sum(ok).astype(jnp.int32),
    )
    return packed, dropped


def route(
    batch: AMBatch,
    *,
    axis: str,
    n_nodes: int,
    per_peer_capacity: int,
    all_to_all_fn: Callable[[jax.Array], jax.Array] | None = None,
    engine=None,
) -> Tuple[AMBatch, jax.Array]:
    """Exchange all nodes' batches; returns each node's *incoming* messages.

    Must be called inside ``shard_map`` over ``axis``.  The transport is,
    in order of preference: ``engine`` (a CommEngine — the exchange is then
    *plan-driven*: ``repro.core.sched`` picks native vs direct-put
    all-to-all from the buffer size and the engine's cost model, so a
    GAScore or mixed node map routes AMs over its own puts), then
    ``all_to_all_fn`` (an explicit callable), then ``lax.all_to_all``.

    The incoming batch has capacity ``n_nodes * K``; slot ``s*K + r`` holds
    the r-th message from source node s.  ``dest`` of received messages is
    rewritten to the *source* node id (GASNet handlers receive the sender's
    identity as the ``token``).
    """
    K = per_peer_capacity
    tr = obs_trace.active()
    if tr.enabled:
        w = batch.payload_width
        tr.instant(
            "am_route", cat="am", n_nodes=n_nodes, capacity=K,
            payload_width=w,
            engine=getattr(engine, "name", None) if engine is not None
            else ("custom" if all_to_all_fn is not None else "lax"),
        )
    packed, dropped = build_send_buffer(batch, n_nodes, K)

    def a2a(x: jax.Array) -> jax.Array:
        if engine is not None:
            from repro.core import sched

            return sched.all_to_all(engine, x)
        if all_to_all_fn is not None:
            return all_to_all_fn(x)
        return lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)

    # dim0 blocks are per-destination; ``dest`` does not travel: on
    # arrival it is rewritten to the source node of each slot
    valid = a2a(packed.valid)
    recv = AMBatch(
        dest=jnp.repeat(jnp.arange(n_nodes, dtype=jnp.int32), K),
        handler=a2a(packed.handler),
        args=a2a(packed.args),
        payload=a2a(packed.payload),
        valid=valid,
        count=jnp.sum(valid).astype(jnp.int32),
    )
    return recv, dropped


# --------------------------------------------------------------------------- #
# Delivery (asynchronous handler invocation, fused)
# --------------------------------------------------------------------------- #
def deliver_with_replies(
    state: Any, recv: AMBatch, handlers: HandlerTable
) -> Tuple[Any, AMBatch]:
    """Apply each landed message's handler to the local state, in slot order,
    and collect the replies of ``replies=True`` handlers.

    Exactly-once: every valid slot fires its handler exactly once; invalid
    slots are skipped.  Implemented as a ``lax.scan`` over slots with a
    ``lax.switch`` over handler ids — sequential like the paper's handler
    queue, which also serializes handler execution per node.

    Returns ``(state, reply_batch)``: slot s of the reply batch is the
    reply (if any) of the request in slot s of ``recv``, addressed at that
    request's sender token — ready for a second :func:`route` hop.
    """
    fns = handlers.fns
    flags = handlers.reply_flags
    if not fns:
        raise ValueError("no handlers registered")
    pw = recv.payload_width
    pdtype = recv.payload.dtype

    def body(st, slot):
        valid, hid, args, payload, token = slot
        del token  # reply routing uses recv.dest directly

        def fire(s):
            branches = []
            for f, rep in zip(fns, flags):
                if rep:
                    branches.append(
                        (lambda f: (lambda ss: f(ss, payload, args)))(f)
                    )
                else:
                    branches.append(
                        (lambda f: (
                            lambda ss: (f(ss, payload, args),
                                        no_reply(pw, pdtype))
                        ))(f)
                    )
            return lax.switch(jnp.clip(hid, 0, len(fns) - 1), branches, s)

        st, rep = lax.cond(
            valid, fire, lambda s: (s, no_reply(pw, pdtype)), st
        )
        return st, rep

    slots = (recv.valid, recv.handler, recv.args, recv.payload, recv.dest)
    state, reps = lax.scan(body, state, slots)
    valid = recv.valid & reps.send
    reply = AMBatch(
        dest=recv.dest,  # the sender token of each request
        handler=reps.handler,
        args=reps.args,
        payload=reps.payload,
        valid=valid,
        count=jnp.sum(valid).astype(jnp.int32),
    )
    return state, reply


def deliver(state: Any, recv: AMBatch, handlers: HandlerTable) -> Any:
    """One-way delivery (see :func:`deliver_with_replies`); replies of any
    ``replies=True`` handlers are discarded — this is the reply-hop rule
    (a reply handler may not reply again)."""
    state, _ = deliver_with_replies(state, recv, handlers)
    return state


def request_reply(
    state: Any,
    batch: AMBatch,
    handlers: HandlerTable,
    *,
    axis: str,
    n_nodes: int,
    per_peer_capacity: int,
    all_to_all_fn: Callable[[jax.Array], jax.Array] | None = None,
    engine=None,
) -> Tuple[Any, jax.Array]:
    """The full GASNet request/reply cycle, statically scheduled:

    1. :func:`route` the request batch (hop 1) and deliver it, collecting
       the ``AMReply`` each request handler returns;
    2. :func:`route` the reply batch back (hop 2) and deliver it one-way
       (nested replies are discarded — GASNet's no-reply-to-a-reply rule).

    The reply hop reuses the same per-peer capacity: a node holds at most
    ``per_peer_capacity`` requests per source, hence at most that many
    replies per destination, so hop 2 can never drop for capacity.
    Returns ``(state, dropped)`` with the hop-1 + hop-2 drop count.
    """
    tr = obs_trace.active()
    with tr.span("am_request_hop", cat="am", n_nodes=n_nodes):
        recv, dropped = route(
            batch, axis=axis, n_nodes=n_nodes,
            per_peer_capacity=per_peer_capacity,
            all_to_all_fn=all_to_all_fn, engine=engine,
        )
        state, replies = deliver_with_replies(state, recv, handlers)
    with tr.span("am_reply_hop", cat="am", n_nodes=n_nodes):
        recv2, dropped2 = route(
            replies, axis=axis, n_nodes=n_nodes,
            per_peer_capacity=per_peer_capacity,
            all_to_all_fn=all_to_all_fn, engine=engine,
        )
        state = deliver(state, recv2, handlers)
    return state, dropped + dropped2


def long_write_handler(seg_key: str) -> Callable:
    """Built-in AMLong handler: GAScore-style remote write of the payload at
    flat offset ``args[0]`` (element count ``args[1]``, 0 = whole payload)
    into ``state[seg_key]`` (any-shaped local segment view)."""

    def fn(state: Any, payload: jax.Array, args: jax.Array) -> Any:
        seg = state[seg_key]
        flat = seg.reshape(-1)
        width = payload.shape[0]
        nelem = jnp.where(args[1] > 0, args[1], width)
        off = args[0]
        cur = lax.dynamic_slice(flat, (off,), (width,))
        mask = jnp.arange(width) < nelem
        new = jnp.where(mask, payload.astype(flat.dtype), cur)
        flat = lax.dynamic_update_slice(flat, new, (off,))
        out = dict(state)
        out[seg_key] = flat.reshape(seg.shape)
        return out

    return fn
