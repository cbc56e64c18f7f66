"""`BulkOps` — the single queue-operation contract, with pluggable backends.

The paper's core contribution is ONE bulk-operation interface
(push / pop / steal as batch ops) whose implementations can be swapped
and benchmarked against each other.  This module is that contract for
the reproduction: every consumer (the virtual master, the unified
executor, the DD solver, the benchmarks) talks to a :class:`BulkOps`
backend object instead of threading ``use_kernel`` booleans through
call sites.

Backends are named and live in a registry:

``"reference"``
    The jnp oracle: plain XLA gathers/scatters, no hand-written
    kernels.  The semantics baseline every other backend is tested
    against, and the path the ``REPRO_QUEUE_BACKEND=reference`` CI lane
    pins to prove independence from Pallas.
``"pallas"``
    Every hot-path op routed through the hand-written Pallas kernels
    (``kernels.queue_steal.ring_gather``, ``kernels.queue_push.
    ring_scatter`` / ``ring_slice``) — Pallas lowering on TPU, the
    kernel modules' jnp oracles elsewhere.  Per-call geometry and
    payload-dtype predicates still gate each op (an unsupported one runs
    that op on the reference path, with a one-shot
    ``BackendFallbackWarning``).
``"auto"``
    Resolves the kernel routing ONCE at construction from the queue
    geometry via the kernel modules' predicates
    (``ring_scatter_supported`` / ``ring_slice_supported`` /
    ``ring_gather_supported``): ops whose geometry the kernels support
    become kernel-backed, the rest stay reference.  No per-call
    branching.
``"relaxed"``
    The fence-free multiplicity-tolerant steal path per Castañeda &
    Piña (``repro.core.relaxed``, registered when ``repro.core``
    imports): optimistic full-window read, bounded over-report,
    posterior reconcile — observationally identical, gated by its own
    geometry predicate.

Operation contract
------------------
Every operation takes the :class:`QueueState` first and returns the new
state first — ``(state, ...) -> (state, batch, n)`` — with the detached
batch (static leading dim, dead rows zeroed) and the dynamic count
following where the op produces them (``push`` returns ``(state,
n_pushed)``: there is no detached batch).  Two exchange-side ops serve
the compact superstep: ``window`` (the victim's raw tail window for the
all_gather — a pure read) and ``transfer`` (the thief's fused
cut-and-splice out of the gathered window stack).  Each op accepts
``donate=True``, which routes through a cached jitted variant whose
input state is donated (XLA aliases the ring buffer input -> output, so
the update is an in-place scatter/cursor bump instead of a full-capacity
copy).  ``donate=False`` (default) composes the pure op inline into the
caller's trace — what ``master.superstep`` does.  This subsumes the old
``*_inplace`` triplets.

``REPRO_QUEUE_BACKEND`` (environment) overrides what ``"auto"``
resolves to — set it to ``reference`` to run any auto-configured
consumer (the executor, the solver, the benchmarks) on the oracle path.
Explicitly requested backends are never overridden.
"""

from __future__ import annotations

import functools
import os
import types
import warnings
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "QueueState",
    "make_queue",
    "queue_size",
    "item_nbytes",
    "BulkOps",
    "make_ops",
    "register_backend",
    "available_backends",
    "steal_counted",
    "kernel_steal_available",
    "kernel_push_available",
    "kernel_pop_available",
    "kernel_transfer_available",
    "DEFAULT_QUEUE_LIMIT",
    "BACKEND_ENV_VAR",
    "BackendFallbackWarning",
    "reset_fallback_warnings",
]

Pytree = Any

# Default abort threshold, mirroring the paper's ``_queue_limit_``.
DEFAULT_QUEUE_LIMIT = 2

# Environment override for what "auto" resolves to (CI's oracle lane).
BACKEND_ENV_VAR = "REPRO_QUEUE_BACKEND"

# Environment switch for the runtime sanitizer (repro.analysis.sanitize):
# REPRO_CHECK=1 makes make_ops wrap every backend in invariant checks.
CHECK_ENV_VAR = "REPRO_CHECK"


class BackendFallbackWarning(UserWarning):
    """A requested routing silently downgraded — ``"auto"`` resolved a
    kernel op to the reference path because a geometry predicate rejected
    the bound, ``"relaxed"`` fell back to the fenced reference routing,
    or ``REPRO_QUEUE_BACKEND`` redirected ``"auto"`` wholesale.  Emitted
    at most once per distinct reason per process (the downgrade is safe —
    observationally identical — but should not be invisible)."""


_FALLBACK_WARNED: set = set()


def _warn_fallback(key: Tuple, message: str) -> None:
    if key in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(key)
    warnings.warn(message, BackendFallbackWarning, stacklevel=4)


def reset_fallback_warnings() -> None:
    """Forget which one-shot fallback warnings already fired (tests)."""
    _FALLBACK_WARNED.clear()


class QueueState(NamedTuple):
    """Immutable queue state.

    Attributes:
      buf:  pytree of ``(capacity, ...)`` arrays holding payloads.
      lo:   int32 physical index of the oldest element (steal side).
      size: int32 number of live elements; owner side is ``(lo+size) % cap``.
    """

    buf: Pytree
    lo: jnp.ndarray
    size: jnp.ndarray


def _capacity(q: QueueState) -> int:
    return jax.tree_util.tree_leaves(q.buf)[0].shape[0]


def _batch_size(batch: Pytree) -> int:
    return jax.tree_util.tree_leaves(batch)[0].shape[0]


def make_queue(capacity: int, item_spec: Pytree) -> QueueState:
    """Create an empty queue.

    Args:
      capacity: static ring capacity.
      item_spec: pytree of ``jax.ShapeDtypeStruct`` (or arrays) describing a
        single item — leaves get a leading ``capacity`` dimension.
    """
    buf = jax.tree_util.tree_map(
        lambda s: jnp.zeros((capacity,) + tuple(s.shape), dtype=s.dtype),
        item_spec,
    )
    return QueueState(buf=buf, lo=jnp.int32(0), size=jnp.int32(0))


def queue_size(q: QueueState) -> jnp.ndarray:
    return q.size


def item_nbytes(item_spec: Pytree) -> int:
    """Bytes per queue item: sum over payload-pytree leaves (arrays or
    ``ShapeDtypeStruct``s describing ONE item, no capacity dimension).
    The single source of truth for item payload accounting — the
    master's ``bytes_moved`` and the runtime telemetry both derive from
    it."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(item_spec):
        n = 1
        for d in leaf.shape:
            n *= int(d)
        total += n * jnp.dtype(leaf.dtype).itemsize
    return total


# ---------------------------------------------------------------------------
# Geometry predicates (the kernel modules own the block-tiling rules)
# ---------------------------------------------------------------------------


def kernel_push_available(capacity: int, max_push: int,
                          dtype=jnp.int32) -> bool:
    """Whether the Pallas ring-scatter kernel can serve a push of this
    geometry and payload dtype."""
    from repro.kernels.queue_push.kernel import ring_scatter_supported

    return ring_scatter_supported(capacity, max_push, dtype=dtype)


def kernel_pop_available(capacity: int, max_n: int,
                         dtype=jnp.int32) -> bool:
    """Whether the Pallas ring-slice kernel can serve a bulk pop of this
    geometry and payload dtype."""
    from repro.kernels.queue_push.kernel import ring_slice_supported

    return ring_slice_supported(capacity, max_n, dtype=dtype)


def kernel_steal_available(capacity: int, max_steal: int,
                           dtype=jnp.int32) -> bool:
    """Whether the Pallas ring-gather kernel can serve a steal of this
    geometry and payload dtype."""
    from repro.kernels.queue_steal.kernel import ring_gather_supported

    return ring_gather_supported(capacity, max_steal, dtype=dtype)


def kernel_transfer_available(capacity: int, max_steal: int,
                              dtype=jnp.int32) -> bool:
    """Whether the Pallas fused ring-transfer kernel can serve the
    compact superstep's thief-side cut-and-splice of this geometry and
    payload dtype."""
    from repro.kernels.queue_transfer.kernel import ring_transfer_supported

    return ring_transfer_supported(capacity, max_steal, dtype=dtype)


def _kernel_routed(op: str, pred, capacity: int, bound: int,
                   buf: Pytree) -> bool:
    """Per-call gate of a kernel-routed op: the predicate must admit
    every payload leaf.  Runs at trace time (static shapes and dtypes);
    an op it sends to the reference path warns once."""
    bad = sorted({str(leaf.dtype) for leaf in jax.tree_util.tree_leaves(buf)
                  if not pred(capacity, bound, leaf.dtype)})
    if bad:
        _warn_fallback(
            ("call", op, capacity, bound, tuple(bad)),
            f"{op} runs on the reference path: the kernel predicate "
            f"rejected capacity={capacity}, bound={bound} for {bad}")
    return not bad


# ---------------------------------------------------------------------------
# Pure op implementations (the single source of truth for semantics)
# ---------------------------------------------------------------------------


def _push(q: QueueState, batch: Pytree, n: jnp.ndarray, *,
          kernel: bool) -> Tuple[QueueState, jnp.ndarray]:
    """Bulk push ``n`` items (owner side).

    ``batch`` leaves have static leading dim ``B >= n``; only the first ``n``
    rows are enqueued.  Returns ``(new_state, n_pushed)`` where ``n_pushed``
    is clamped to the available space.  Cost: one masked ring-scatter —
    O(B) vectorized, constant per item.  The ``size + n`` update is the
    linearization point.
    """
    cap = _capacity(q)
    bsz = _batch_size(batch)
    n = jnp.minimum(jnp.asarray(n, jnp.int32), jnp.int32(cap) - q.size)
    n = jnp.maximum(n, 0)
    if kernel and _kernel_routed("push", kernel_push_available, cap,
                                 bsz, q.buf):
        from repro.kernels.queue_push.ops import push_scatter

        buf = push_scatter(
            q.buf, batch, (q.lo + q.size) % cap, n,
            use_pallas=jax.default_backend() == "tpu",
        )
        return QueueState(buf=buf, lo=q.lo, size=q.size + n), n
    offs = jnp.arange(bsz, dtype=jnp.int32)
    phys = (q.lo + q.size + offs) % cap
    # Rows beyond ``n`` are routed out of bounds and dropped.
    phys = jnp.where(offs < n, phys, cap)
    buf = jax.tree_util.tree_map(
        lambda b, x: b.at[phys].set(x, mode="drop"), q.buf, batch
    )
    return QueueState(buf=buf, lo=q.lo, size=q.size + n), n


def _pop(q: QueueState) -> Tuple[QueueState, Pytree, jnp.ndarray]:
    """Pop the newest item (owner side, LIFO).

    Returns ``(new_state, item, valid)``; ``item`` is arbitrary when
    ``valid`` is False (queue empty) — the null-pointer analogue.
    """
    cap = _capacity(q)
    valid = q.size > 0
    idx = (q.lo + jnp.maximum(q.size - 1, 0)) % cap
    item = jax.tree_util.tree_map(lambda b: b[idx], q.buf)
    new_size = jnp.where(valid, q.size - 1, q.size)
    return QueueState(buf=q.buf, lo=q.lo, size=new_size), item, valid


def _mask_batch(batch: Pytree, live: jnp.ndarray, rows: int) -> Pytree:
    def _m(x):
        shape = (rows,) + (1,) * (x.ndim - 1)
        return jnp.where(live.reshape(shape), x, jnp.zeros_like(x))

    return jax.tree_util.tree_map(_m, batch)


def _pop_bulk(q: QueueState, max_n: int, n: jnp.ndarray, *,
              kernel: bool) -> Tuple[QueueState, Pytree, jnp.ndarray]:
    """Bulk pop up to ``n`` newest items (owner side).

    Returns ``(new_state, batch, n_popped)``; ``batch`` leaves have static
    leading dim ``max_n`` with valid rows ``[0, n_popped)`` in queue order
    (oldest of the popped block first) and rows ``>= n_popped`` zeroed
    (safe for summing collectives, identical across backends).
    """
    cap = _capacity(q)
    n = jnp.minimum(jnp.minimum(jnp.asarray(n, jnp.int32), q.size), max_n)
    n = jnp.maximum(n, 0)
    if kernel and _kernel_routed("pop_bulk", kernel_pop_available, cap,
                                 max_n, q.buf):
        from repro.kernels.queue_push.ops import pop_slice

        batch = pop_slice(
            q.buf, q.lo, q.size, n, max_n=max_n,
            use_pallas=jax.default_backend() == "tpu",
        )
        return QueueState(buf=q.buf, lo=q.lo, size=q.size - n), batch, n
    offs = jnp.arange(max_n, dtype=jnp.int32)
    start = q.size - n  # logical offset of the popped block
    phys = (q.lo + start + offs) % cap
    batch = jax.tree_util.tree_map(lambda b: b[phys], q.buf)
    batch = _mask_batch(batch, offs < n, max_n)
    return QueueState(buf=q.buf, lo=q.lo, size=q.size - n), batch, n


def _gather_block(q: QueueState, n: jnp.ndarray, max_steal: int,
                  kernel: bool) -> Pytree:
    """Detach ``max_steal`` rows starting at ``lo`` (rows >= ``n`` zeroed).

    ``kernel=True`` routes the copy through
    :func:`repro.kernels.queue_steal.ops.steal_gather` (Pallas on TPU,
    the jnp oracle elsewhere); ``kernel=False`` keeps the inline gather
    (still used by the counted baseline so Fig. 8 measures what it
    claims to).
    """
    cap = _capacity(q)
    if kernel and _kernel_routed("steal", kernel_steal_available, cap,
                                 max_steal, q.buf):
        from repro.kernels.queue_steal.ops import steal_gather

        return steal_gather(
            q.buf, q.lo, n, max_steal=max_steal,
            use_pallas=jax.default_backend() == "tpu",
        )
    offs = jnp.arange(max_steal, dtype=jnp.int32)
    phys = (q.lo + offs) % cap
    batch = jax.tree_util.tree_map(lambda b: b[phys], q.buf)
    return _mask_batch(batch, offs < n, max_steal)


def _steal_plan(
    size: jnp.ndarray, proportion, queue_limit: int, max_steal: int
) -> jnp.ndarray:
    """Number of items to steal, following the paper's Listing 4 arithmetic.

    ``n_skip = floor(size * (1 - proportion))`` items remain with the owner;
    ``size - n_skip`` are stolen, clamped to the static transfer buffer.
    Aborts (returns 0) when ``size < queue_limit``.
    """
    size = jnp.asarray(size, jnp.int32)
    keep = jnp.asarray(
        jnp.floor(size.astype(jnp.float32) * (1.0 - proportion)), jnp.int32
    )
    # Clamp to [0, min(size, max_steal)]: proportions outside [0, 1]
    # (e.g. a paging caller spilling "up to half the ring" of a nearly
    # empty queue) must never detach more items than exist — a negative
    # size corrupts the ring.
    n = jnp.clip(size - keep, 0, jnp.minimum(size, jnp.int32(max_steal)))
    return jnp.where(size < queue_limit, jnp.int32(0), n)


def _steal(q: QueueState, proportion, *, max_steal: int, queue_limit: int,
           kernel: bool) -> Tuple[QueueState, Pytree, jnp.ndarray]:
    """Bulk steal of ``~proportion`` of the queue from the tail (oldest side).

    The paper's *optimized* variant, which on TPU is the natural one: the
    stolen count is fully determined by the size snapshot and the cut
    arithmetic, so no tail traversal is ever needed.  The single
    ``lo += n`` cursor bump is the linearization point (the analogue of
    the ``start->next = null`` severing write).
    """
    cap = _capacity(q)
    n = _steal_plan(q.size, proportion, queue_limit, max_steal)
    batch = _gather_block(q, n, max_steal, kernel)
    new_lo = (q.lo + n) % cap
    return QueueState(buf=q.buf, lo=new_lo, size=q.size - n), batch, n


def _steal_exact(q: QueueState, n: jnp.ndarray, *, max_steal: int,
                 kernel: bool) -> Tuple[QueueState, Pytree, jnp.ndarray]:
    """Steal exactly ``n`` items (clamped to size / ``max_steal``) from the
    tail.  Used by the virtual master once the plan has fixed per-victim
    amounts; rows ``>= n`` of the returned batch are zeroed so the batch
    can move through summing collectives safely."""
    n = jnp.clip(jnp.asarray(n, jnp.int32), 0, jnp.minimum(q.size, max_steal))
    cap = _capacity(q)
    batch = _gather_block(q, n, max_steal, kernel)
    new_lo = (q.lo + n) % cap
    return QueueState(buf=q.buf, lo=new_lo, size=q.size - n), batch, n


def _window(q: QueueState, *, max_steal: int, kernel: bool) -> Pytree:
    """Raw tail window: rows ``(lo + i) % cap`` for ``i < max_steal``,
    UNMASKED (rows past ``size`` carry whatever the ring holds — they
    are dead weight the compact superstep's all_gather carries and the
    thief never reads).  This is the victim-side contribution to the
    compact exchange: the detach itself is a pure cursor bump, so no
    masked intermediate is ever materialized."""
    return _gather_block(q, jnp.int32(max_steal), max_steal, kernel)


def _transfer(q: QueueState, gathered: Pytree, src_row, n, *,
              max_steal: int, kernel: bool
              ) -> Tuple[QueueState, jnp.ndarray]:
    """Thief-side fused cut-and-splice for the compact superstep: splice
    rows ``gathered[src_row, :n]`` (each ``gathered`` leaf is a
    ``(W, max_steal, ...)`` stack of per-lane windows) at the owner end
    of ``q``.  Semantically ``push(q, gathered[src_row], n)``; the
    kernel path (``kernels.queue_transfer.ring_transfer``) reads the
    gathered buffer directly through a dynamic source offset so the
    selected ``(max_steal, ...)`` block never materializes.  Returns
    ``(new_state, n_spliced)`` with ``n`` clamped to the available
    space, exactly like ``push``."""
    cap = _capacity(q)
    src_row = jnp.asarray(src_row, jnp.int32)
    n = jnp.minimum(jnp.asarray(n, jnp.int32),
                    jnp.minimum(jnp.int32(cap) - q.size,
                                jnp.int32(max_steal)))
    n = jnp.maximum(n, 0)
    if kernel and _kernel_routed("transfer", kernel_transfer_available,
                                 cap, max_steal, q.buf):
        from repro.kernels.queue_transfer.ops import transfer_splice

        buf = transfer_splice(
            q.buf, gathered, (q.lo + q.size) % cap, src_row, n,
            max_steal=max_steal,
            use_pallas=jax.default_backend() == "tpu",
        )
        return QueueState(buf=buf, lo=q.lo, size=q.size + n), n
    # Reference path IS "select the victim's row, then push" — delegate
    # so the ring-splice write has one source of truth (_push).
    batch = jax.tree_util.tree_map(
        lambda g: lax.dynamic_index_in_dim(g, src_row, 0, keepdims=False),
        gathered,
    )
    return _push(q, batch, n, kernel=False)


def steal_counted(
    q: QueueState,
    proportion,
    *,
    max_steal: int,
    queue_limit: int = DEFAULT_QUEUE_LIMIT,
) -> Tuple[QueueState, Pytree, jnp.ndarray]:
    """Paper-faithful *non-optimized* steal: pays an explicit sequential
    traversal over the stolen segment to (re)count it, mirroring the second
    list walk in Listing 4 lines 30-37.  Semantically identical to the
    backends' ``steal``; exists so benchmarks can reproduce Fig. 8's gap.
    Always the reference gather — it measures the baseline's cost shape.
    """
    new_q, batch, n = _steal(q, proportion, max_steal=max_steal,
                             queue_limit=queue_limit, kernel=False)
    # Sequential dependent chain emulating pointer-chasing: each step reads
    # a payload element gated by the previous counter value, so XLA cannot
    # vectorize or elide it.
    lead = jax.tree_util.tree_leaves(batch)[0]
    flat = lead.reshape(lead.shape[0], -1)

    def body(i, carry):
        count, acc = carry
        live = i < n
        probe = flat[i, 0].astype(jnp.float32)
        acc = acc + jnp.where(live, probe * 0.0 + 1.0, 0.0) * (count + 1.0) * 0.0
        count = count + jnp.where(live, 1, 0)
        return count, acc

    count, acc = lax.fori_loop(0, max_steal, body, (jnp.int32(0), jnp.float32(0.0)))
    # ``count == n`` always; fold the dead value in so the loop is not DCE'd.
    n = count + jnp.asarray(acc, jnp.int32) * 0
    return new_q, batch, n


# ---------------------------------------------------------------------------
# Donating (in-place) variants — jitted once per (routing, geometry)
# ---------------------------------------------------------------------------
#
# The pure ops above copy-on-write the full-capacity ring every call when
# used as plain host-called functions.  The donating variants jit them
# with the queue state DONATED, so XLA aliases the input ring buffer to
# the output and the update lowers to an in-place scatter/cursor bump.
# Semantics are identical (tests assert equivalence); the caller must not
# reuse the donated input state afterwards.  Donation is a no-op (with
# identical results) on backends that don't implement it (CPU) — the
# call is still jitted, so host-driven loops pay one dispatch, not a
# retrace.


@functools.lru_cache(maxsize=None)
def _donating(kernel_push: bool, kernel_pop: bool, kernel_steal: bool,
              kernel_transfer: bool) -> types.SimpleNamespace:
    donate = () if jax.default_backend() == "cpu" else (0,)
    return types.SimpleNamespace(
        push=jax.jit(functools.partial(_push, kernel=kernel_push),
                     donate_argnums=donate),
        pop=jax.jit(_pop, donate_argnums=donate),
        pop_bulk=jax.jit(functools.partial(_pop_bulk, kernel=kernel_pop),
                         static_argnums=(1,), donate_argnums=donate),
        steal=jax.jit(functools.partial(_steal, kernel=kernel_steal),
                      static_argnames=("max_steal", "queue_limit"),
                      donate_argnums=donate),
        steal_exact=jax.jit(
            functools.partial(_steal_exact, kernel=kernel_steal),
            static_argnames=("max_steal",), donate_argnums=donate),
        window=jax.jit(functools.partial(_window, kernel=kernel_steal),
                       static_argnames=("max_steal",)),
        transfer=jax.jit(
            functools.partial(_transfer, kernel=kernel_transfer),
            static_argnames=("max_steal",), donate_argnums=donate),
    )


# ---------------------------------------------------------------------------
# The backend object
# ---------------------------------------------------------------------------


class BulkOps:
    """One queue-operation backend: the paper's bulk push/pop/steal
    contract with a fixed kernel routing.

    Instances are cheap, stateless value objects — the four ``kernel_*``
    booleans are the entire configuration, fixed at construction (this is
    where ``"auto"``'s geometry resolution happens, never per call).
    Obtain instances via :func:`make_ops`; compare routing with
    :attr:`resolved` (``"reference"`` / ``"pallas"`` / ``"mixed"``).
    """

    def __init__(self, name: str, *, kernel_push: bool = False,
                 kernel_pop: bool = False, kernel_steal: bool = False,
                 kernel_transfer: bool = False):
        self.name = name
        self.kernel_push = bool(kernel_push)
        self.kernel_pop = bool(kernel_pop)
        self.kernel_steal = bool(kernel_steal)
        self.kernel_transfer = bool(kernel_transfer)

    @property
    def resolved(self) -> str:
        """The effective routing: which implementation family serves ops."""
        flags = self._flags()
        if all(flags):
            return "pallas"
        if not any(flags):
            return "reference"
        return "mixed"

    def __repr__(self) -> str:
        return (f"BulkOps({self.name!r}, push={self.kernel_push}, "
                f"pop={self.kernel_pop}, steal={self.kernel_steal}, "
                f"transfer={self.kernel_transfer})")

    def __eq__(self, other) -> bool:
        return (isinstance(other, BulkOps)
                and self._flags() == other._flags())

    def __hash__(self) -> int:
        return hash(self._flags())

    def _flags(self) -> Tuple[bool, bool, bool, bool]:
        return (self.kernel_push, self.kernel_pop, self.kernel_steal,
                self.kernel_transfer)

    # -- operations ----------------------------------------------------------

    def push(self, q: QueueState, batch: Pytree, n, *,
             donate: bool = False) -> Tuple[QueueState, jnp.ndarray]:
        """Bulk push ``n`` items; returns ``(state, n_pushed)``."""
        if donate:
            return _donating(*self._flags()).push(q, batch, n)
        with jax.named_scope("queue.push"):
            return _push(q, batch, n, kernel=self.kernel_push)

    def pop(self, q: QueueState, *, donate: bool = False
            ) -> Tuple[QueueState, Pytree, jnp.ndarray]:
        """Pop the newest item; returns ``(state, item, valid)``."""
        if donate:
            return _donating(*self._flags()).pop(q)
        return _pop(q)

    def pop_bulk(self, q: QueueState, max_n: int, n, *,
                 donate: bool = False
                 ) -> Tuple[QueueState, Pytree, jnp.ndarray]:
        """Bulk pop up to ``n`` newest items; returns
        ``(state, batch, n_popped)`` with ``batch`` rows >= n zeroed."""
        if donate:
            return _donating(*self._flags()).pop_bulk(q, max_n, n)
        with jax.named_scope("queue.pop"):
            return _pop_bulk(q, max_n, n, kernel=self.kernel_pop)

    def steal(self, q: QueueState, proportion, *, max_steal: int,
              queue_limit: int = DEFAULT_QUEUE_LIMIT,
              donate: bool = False
              ) -> Tuple[QueueState, Pytree, jnp.ndarray]:
        """Proportional bulk steal from the tail; returns
        ``(state, batch, n_stolen)``."""
        if donate:
            return _donating(*self._flags()).steal(
                q, proportion, max_steal=max_steal, queue_limit=queue_limit)
        return _steal(q, proportion, max_steal=max_steal,
                      queue_limit=queue_limit, kernel=self.kernel_steal)

    def steal_exact(self, q: QueueState, n, *, max_steal: int,
                    donate: bool = False
                    ) -> Tuple[QueueState, Pytree, jnp.ndarray]:
        """Steal exactly ``n`` items (clamped); returns
        ``(state, batch, n_stolen)``."""
        if donate:
            return _donating(*self._flags()).steal_exact(
                q, n, max_steal=max_steal)
        return _steal_exact(q, n, max_steal=max_steal,
                            kernel=self.kernel_steal)

    def window(self, q: QueueState, *, max_steal: int,
               donate: bool = False) -> Pytree:
        """Raw (unmasked) ``max_steal``-row tail window at ``lo`` — the
        victim-side contribution to the compact superstep's all_gather.
        Pure read: the state is unchanged (the victim's detach is the
        caller's cursor bump)."""
        if donate:
            return _donating(*self._flags()).window(q, max_steal=max_steal)
        with jax.named_scope("queue.window"):
            return _window(q, max_steal=max_steal, kernel=self.kernel_steal)

    def transfer(self, q: QueueState, gathered: Pytree, src_row, n, *,
                 max_steal: int, donate: bool = False
                 ) -> Tuple[QueueState, jnp.ndarray]:
        """Fused thief-side cut-and-splice: push ``gathered[src_row, :n]``
        (leaves ``(W, max_steal, ...)``) at the owner end without
        materializing the selected block; returns ``(state, n_spliced)``."""
        if donate:
            return _donating(*self._flags()).transfer(
                q, gathered, src_row, n, max_steal=max_steal)
        with jax.named_scope("queue.transfer"):
            return _transfer(q, gathered, src_row, n, max_steal=max_steal,
                             kernel=self.kernel_transfer)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

# A factory takes the geometry kwargs and returns a configured BulkOps.
BackendFactory = Callable[..., BulkOps]

_REGISTRY: Dict[str, BackendFactory] = {}


def register_backend(name: str, factory: BackendFactory) -> None:
    """Register a named backend factory.  The factory receives the
    geometry keywords of :func:`make_ops` (``capacity`` / ``max_push`` /
    ``max_pop`` / ``max_steal``, each possibly ``None``)."""
    _REGISTRY[name] = factory


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def _reference_factory(**_geometry) -> BulkOps:
    return BulkOps("reference")


def _pallas_factory(**_geometry) -> BulkOps:
    return BulkOps("pallas", kernel_push=True, kernel_pop=True,
                   kernel_steal=True, kernel_transfer=True)


def _auto_factory(*, capacity: Optional[int] = None,
                  max_push: Optional[int] = None,
                  max_pop: Optional[int] = None,
                  max_steal: Optional[int] = None) -> BulkOps:
    """Resolve the kernel routing once, from the geometry predicates.
    Unknown geometry components conservatively stay on the reference
    path (no per-call probing)."""
    def ok(op, pred, bound):
        if capacity is None or bound is None:
            return False  # unknown geometry: documented reference default
        if pred(capacity, bound):
            return True
        _warn_fallback(
            ("auto", op, capacity, bound),
            f"auto: {op} falls back to the reference path — the kernel "
            f"geometry predicate rejected capacity={capacity}, "
            f"bound={bound} (block tiling does not divide the ring)")
        return False

    return BulkOps(
        "auto",
        kernel_push=ok("push", kernel_push_available, max_push),
        kernel_pop=ok("pop_bulk", kernel_pop_available, max_pop),
        kernel_steal=ok("steal", kernel_steal_available, max_steal),
        kernel_transfer=ok("transfer", kernel_transfer_available, max_steal),
    )


register_backend("reference", _reference_factory)
register_backend("pallas", _pallas_factory)
register_backend("auto", _auto_factory)


def _env_check() -> bool:
    return os.environ.get(CHECK_ENV_VAR, "").strip().lower() in (
        "1", "true", "yes", "on")


def make_ops(backend: Optional[str] = "auto", *,
             capacity: Optional[int] = None,
             max_push: Optional[int] = None,
             max_pop: Optional[int] = None,
             max_steal: Optional[int] = None,
             check: Optional[bool] = None) -> BulkOps:
    """Construct a :class:`BulkOps` backend.

    ``backend`` is a registry name (``"reference"`` / ``"pallas"`` /
    ``"auto"`` / anything registered) or an existing :class:`BulkOps`
    (returned unchanged, so call sites can accept either).  ``"auto"``
    (also the ``backend=None`` default) resolves its kernel routing HERE,
    once, from the geometry keywords — and honours the
    ``REPRO_QUEUE_BACKEND`` environment override; explicit names are
    never overridden.

    ``check=True`` (default: the ``REPRO_CHECK`` environment switch)
    wraps the backend in the runtime sanitizer
    (``repro.analysis.sanitize.CheckedBulkOps``): every op validated
    against the sequential contract — conservation, cursor monotonicity,
    dead rows zeroed — eagerly on concrete states, via
    ``jax.debug.callback`` scalar checks under a trace.
    """
    if check is None:
        check = _env_check()
    if isinstance(backend, BulkOps):
        return _maybe_checked(backend, check)
    if backend is None:
        backend = "auto"
    if backend == "auto":
        env = os.environ.get(BACKEND_ENV_VAR, "").strip()
        if env and env != "auto":
            _warn_fallback(
                ("env", env),
                f"auto resolved to {env!r} via the {BACKEND_ENV_VAR} "
                f"environment override, not geometry routing")
            backend = env
    try:
        factory = _REGISTRY[backend]
    except KeyError:
        raise ValueError(
            f"unknown queue backend {backend!r}; "
            f"available: {available_backends()}") from None
    ops = factory(capacity=capacity, max_push=max_push, max_pop=max_pop,
                  max_steal=max_steal)
    return _maybe_checked(ops, check)


def _maybe_checked(ops: BulkOps, check: bool) -> BulkOps:
    if not check:
        return ops
    from repro.analysis.sanitize import CheckedBulkOps  # deferred: no cycle

    if isinstance(ops, CheckedBulkOps):
        return ops
    return CheckedBulkOps(ops)
