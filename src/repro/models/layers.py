"""Shared building blocks: sharding helpers, norms, embeddings, MLPs.

All models are pure functions over parameter pytrees (dicts of jnp arrays).
Scanned layer stacks carry a leading ``(L, ...)`` dimension.  Sharding is
expressed through :func:`shard`, which applies a
``with_sharding_constraint`` only when a mesh context is active — so the
exact same model code runs un-annotated on a bare CPU (smoke tests) and
fully sharded under the production mesh (dry-run / launcher).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Pytree = Any

__all__ = [
    "shard",
    "axes",
    "ShardPlan",
    "rms_norm",
    "softcap",
    "dense_init",
    "embed_init",
    "mlp_init",
    "mlp_apply",
    "cross_entropy",
    "chunked_ce_loss",
]


# Roofline accounting mode: XLA's cost_analysis counts a while-loop body
# ONCE, not x trip-count, so scanned-layer FLOPs/bytes/collectives would be
# undercounted ~n_layers-fold.  The dry-run sets this True to lower a fully
# unrolled variant purely for cost extraction (the scanned program remains
# the production/memory artifact).
SCAN_UNROLL = False


def set_scan_unroll(flag: bool) -> None:
    global SCAN_UNROLL
    SCAN_UNROLL = bool(flag)


def pscan(body, init, xs, length=None):
    """lax.scan honoring the roofline unroll switch."""
    return jax.lax.scan(body, init, xs, length=length,
                        unroll=True if SCAN_UNROLL else 1)


def _active_mesh():
    """The (abstract) mesh installed by ``with jax.set_mesh(mesh):``, if
    any; readable inside ``jax.jit``, where shardings and ``shard_map``
    resolve against it.  None inside a ``shard_map`` body, whose axes
    are Manual: there the model code runs per shard, unconstrained."""
    m = jax.sharding.get_abstract_mesh()
    auto = all(t == jax.sharding.AxisType.Auto for t in m.axis_types)
    return None if m.empty or not auto else m


def shard(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """``with_sharding_constraint(x, P(*spec))`` under an active mesh;
    identity otherwise.  Entries naming axes absent from the active mesh
    are dropped (so single-pod and multi-pod share one model code path)."""
    m = _active_mesh()
    if m is None:
        return x
    names = set(m.axis_names)

    def _filter(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in names)
            return kept if kept else None
        return e if e in names else None

    return jax.lax.with_sharding_constraint(x, P(*[_filter(e) for e in spec]))


class ShardPlan:
    """Named axis roles for a parallelism plan (see configs.ParallelConfig).

    dp:   batch axes (tuple — includes the pod axis on multi-pod meshes)
    tp:   tensor-parallel axis (heads / d_ff / vocab / experts / seq-SP)
    fsdp: parameter-sharding axis (None => replicated params, pure DP)
    """

    def __init__(self, dp: Tuple[str, ...] = ("data",), tp: str = "model",
                 fsdp: Optional[str] = "data"):
        self.dp, self.tp, self.fsdp = tuple(dp), tp, fsdp

    @classmethod
    def from_parallel(cls, par) -> "ShardPlan":
        return cls(dp=par.batch_axes, tp=par.model_axis, fsdp=par.fsdp_axis)


# Default plan used when models are called without explicit plan.
axes = ShardPlan()


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    """Gemma2 logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean token CE; logits in fp32 for a stable softmax."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_ce_loss(hidden: jnp.ndarray, head: jnp.ndarray,
                    labels: jnp.ndarray, mask: Optional[jnp.ndarray],
                    sh: "ShardPlan", *, final_softcap: Optional[float] = None,
                    chunk: int = 512, remat: bool = True) -> jnp.ndarray:
    """LM head + CE in sequence chunks so (B, S, V) never materializes.

    hidden: (B, S, D); head: (D, V).  V can be 256k: a chunk's logits are
    (B, chunk, V) f32, sharded over (dp, -, tp).
    """
    from repro.models.layers import softcap as _softcap  # self-import ok

    B, S, D = hidden.shape
    nchunk = max(S // chunk, 1)
    while S % nchunk:           # nchunk must divide S (e.g. vlm's S=3840)
        nchunk -= 1
    csz = S // nchunk
    hs = jnp.moveaxis(hidden.reshape(B, nchunk, csz, D), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, nchunk, csz), 1, 0)
    ms = (jnp.moveaxis(mask.reshape(B, nchunk, csz), 1, 0).astype(jnp.float32)
          if mask is not None
          else jnp.ones((nchunk, B, csz), jnp.float32))

    def chunk_loss(carry, inp):
        h, l, m = inp
        logits = jnp.einsum("bsd,dv->bsv", h, head)
        logits = _softcap(logits, final_softcap)
        logits = shard(logits, sh.dp, None, sh.tp)
        lo = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(lo, axis=-1)
        gold = jnp.take_along_axis(lo, l[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * m
        return (carry[0] + jnp.sum(nll), carry[1] + jnp.sum(m)), None

    body = chunk_loss
    if remat:
        body = jax.checkpoint(
            chunk_loss, policy=jax.checkpoint_policies.nothing_saveable)
    (tot, cnt), _ = pscan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (hs, ls, ms))
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# initializers (smoke-test scale only; dry-run uses eval_shape)
# ---------------------------------------------------------------------------


def dense_init(key, shape: Sequence[int], dtype, scale: float = 0.02) -> jnp.ndarray:
    return (jax.random.normal(key, tuple(shape), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


def mlp_init(key, L: int, d_model: int, d_ff: int, dtype) -> Pytree:
    """SwiGLU MLP, stacked over L layers."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (L, d_model, d_ff), dtype),
        "w_up": dense_init(k2, (L, d_model, d_ff), dtype),
        "w_down": dense_init(k3, (L, d_ff, d_model), dtype),
    }


def mlp_apply(p: Pytree, x: jnp.ndarray, sh: ShardPlan, compute_dtype) -> jnp.ndarray:
    """SwiGLU: down(silu(gate(x)) * up(x)). p leaves are per-layer (no L dim)."""
    x = x.astype(compute_dtype)
    h = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(compute_dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(compute_dtype))
    h = shard(jax.nn.silu(h) * u, sh.dp, None, sh.tp)
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(compute_dtype))
    return shard(out, sh.dp, None, None)
