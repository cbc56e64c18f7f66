"""GQA attention with RoPE / sliding-window / softcap / q-k norm + KV cache.

One implementation covers every assigned attention flavour:

* GQA with any (n_heads, n_kv_heads) ratio — KV heads are broadcast over
  query groups (Megatron-style; KV heads replicate across TP when
  ``n_kv_heads < tp``).
* RoPE with a configurable rotary fraction (chatglm3's "2d" RoPE rotates
  half of each head; everyone else uses fraction 1.0) and theta.
* Sliding-window masks (mixtral, gemma2 local layers) and full-causal.
* Gemma2 attention-logit soft-capping and qwen3 per-head q/k RMSNorm.
* Cross-attention (seamless enc-dec) — no causal mask, KV from encoder.
* Decode with a ring KV cache for windowed layers (cache length
  ``min(window, seq)``) and a linear cache otherwise.

The jnp path below is the lowering/compile reference; on TPU the
``kernels/flash_attention`` Pallas kernel implements the same math with
VMEM block tiling (selected via ``ModelConfig.use_pallas``).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import (ShardPlan, _active_mesh, dense_init,
                                 rms_norm, shard, softcap, pscan)

Pytree = Any

__all__ = [
    "AttnConfig",
    "attn_init",
    "rope",
    "attention",
    "decode_attention",
    "KVCache",
]


class AttnConfig(NamedTuple):
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10_000.0
    rope_fraction: float = 1.0
    window: Optional[int] = None          # None => full causal
    softcap: Optional[float] = None
    qk_norm: bool = False
    causal: bool = True                   # False for encoder / cross attn


def attn_init(key, L: int, d_model: int, cfg: AttnConfig, dtype) -> Pytree:
    """Parameters for L stacked layers (L==1 ⇒ squeeze by caller if wanted)."""
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (L, d_model, H * hd), dtype),
        "wk": dense_init(ks[1], (L, d_model, K * hd), dtype),
        "wv": dense_init(ks[2], (L, d_model, K * hd), dtype),
        "wo": dense_init(ks[3], (L, H * hd, d_model), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((L, hd), dtype)
        p["k_norm"] = jnp.ones((L, hd), dtype)
    return p


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         fraction: float = 1.0) -> jnp.ndarray:
    """Apply rotary embedding to the first ``fraction`` of each head.

    x: (B, S, H, hd); positions: (B, S) or (S,).
    """
    hd = x.shape[-1]
    rot = int(hd * fraction) // 2 * 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]   # (S, half)
        ang = ang[None, :, None, :]                                      # (1,S,1,half)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs           # (B,S,half)
        ang = ang[:, :, None, :]                                         # (B,S,1,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = xr[..., :half].astype(jnp.float32), xr[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------


def _mask_bias(q_pos: jnp.ndarray, k_pos: jnp.ndarray, causal: bool,
               window: Optional[int]) -> jnp.ndarray:
    """(Sq, Sk) additive bias: 0 where attendable, -inf elsewhere."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


# ---------------------------------------------------------------------------
# core attention (training / prefill)
# ---------------------------------------------------------------------------

# KV lengths at or above this use the lax.scan flash-style path so the
# (S, T) logits tensor is never materialized (prefill_32k would otherwise
# need ~17 GB/device of logits; even train_4k's direct path holds ~8 GiB
# of f32 logits per device).  The Pallas kernel replaces this on TPU.
_BLOCKED_KV_THRESHOLD = 4096
_KV_BLOCK = 1024


def _expand_kv(k: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """GQA -> flat heads: (B,T,K,hd) -> (B,T,H,hd), Megatron-style KV-head
    replication.  H is divisible by the 16-way TP axis for every assigned
    arch (K often is not), so sharding stays conflict-free; each TP rank
    only materializes the expanded heads it owns."""
    K = k.shape[2]
    if K == n_heads:
        return k
    return jnp.repeat(k, n_heads // K, axis=2)


def _blocked_attention(q, k, v, q_pos, k_pos, causal, window, cap,
                       compute_dtype, sh: ShardPlan):
    """Flash-style attention: scan over KV blocks with running (m, l, acc).

    q: (B,S,H,hd); k, v: (B,T,H,hd) (already expanded). Returns (B,S,H,hd).
    """
    B, S, H, hd = q.shape
    T = k.shape[1]
    nb = T // _KV_BLOCK
    kb = jnp.moveaxis(k.reshape(B, nb, _KV_BLOCK, H, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, _KV_BLOCK, H, hd), 1, 0)
    kpb = k_pos.reshape(nb, _KV_BLOCK)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    def step(carry, inp):
        m, l, acc = carry
        kc, vc, kp = inp
        logits = jnp.einsum("bshd,bthd->bhst", q, kc).astype(jnp.float32)
        logits = logits * scale
        logits = softcap(logits, cap)
        logits = shard(logits, sh.dp, sh.tp, None, None)
        ok = jnp.ones((S, _KV_BLOCK), bool)
        if causal:
            ok &= kp[None, :] <= q_pos[:, None]
        if window is not None:
            ok &= kp[None, :] > q_pos[:, None] - window
        logits = jnp.where(ok[None, None], logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # guard: fully-masked rows keep m = -inf; exp(-inf - -inf) -> use safe m
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - m_safe[..., None])
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhst,bthd->bshd", p.astype(compute_dtype), vc)
        acc_new = acc * jnp.moveaxis(corr, 1, 2)[..., None] + pv.astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, S, H, hd), jnp.float32)
    # Remat each block step: scan-bwd then saves only the small (m, l, acc)
    # carries + the kv block slices instead of stacked f32 logits/masks
    # (those stacked residuals were ~2 GiB/device at train_4k).
    step = jax.checkpoint(step, policy=jax.checkpoint_policies.nothing_saveable)
    (m, l, acc), _ = pscan(step, (m0, l0, a0), (kb, vb, kpb))
    denom = jnp.moveaxis(jnp.maximum(l, 1e-30), 1, 2)[..., None]
    return (acc / denom).astype(compute_dtype)


def attention(p: Pytree, x: jnp.ndarray, cfg: AttnConfig, sh: ShardPlan,
              compute_dtype, positions: Optional[jnp.ndarray] = None,
              kv_x: Optional[jnp.ndarray] = None,
              kv_positions: Optional[jnp.ndarray] = None,
              return_kv: bool = False):
    """Full attention over a (B, S, D) block.

    kv_x: source for K/V (cross-attention); defaults to x (self-attention).
    Returns (B, S, D) output, optionally also the (k, v) tensors for cache
    construction during prefill.
    """
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    src = x if kv_x is None else kv_x
    Sk = src.shape[1]
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    if kv_positions is None:
        kv_positions = positions if kv_x is None else jnp.arange(Sk, dtype=jnp.int32)

    xc = x.astype(compute_dtype)
    sc = src.astype(compute_dtype)
    q = jnp.einsum("bsd,dh->bsh", xc, p["wq"].astype(compute_dtype)).reshape(B, S, H, hd)
    k = jnp.einsum("bsd,dh->bsh", sc, p["wk"].astype(compute_dtype)).reshape(B, Sk, K, hd)
    v = jnp.einsum("bsd,dh->bsh", sc, p["wv"].astype(compute_dtype)).reshape(B, Sk, K, hd)

    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if cfg.rope_fraction > 0 and kv_x is None:  # no RoPE on cross-attn
        q = rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = rope(k, kv_positions, cfg.rope_theta, cfg.rope_fraction)

    q = shard(q, sh.dp, None, sh.tp, None)
    k0, v0 = k, v  # unexpanded (B,T,K,hd) — what a KV cache stores
    # GQA: expand KV to flat H heads (Megatron-style; see _expand_kv).
    k = shard(_expand_kv(k, H), sh.dp, None, sh.tp, None)
    v = shard(_expand_kv(v, H), sh.dp, None, sh.tp, None)

    q_pos = positions if positions.ndim == 1 else positions[0]
    k_pos = kv_positions if kv_positions.ndim == 1 else kv_positions[0]
    causal = cfg.causal and kv_x is None
    if Sk >= _BLOCKED_KV_THRESHOLD and Sk % _KV_BLOCK == 0:
        o = _blocked_attention(q, k, v, q_pos, k_pos, causal, cfg.window,
                               cfg.softcap, compute_dtype, sh)
        o = o.reshape(B, S, H * hd)
    else:
        logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32)
        logits = logits / jnp.sqrt(hd).astype(jnp.float32)
        logits = softcap(logits, cfg.softcap)
        logits = shard(logits, sh.dp, sh.tp, None, None)
        bias = _mask_bias(q_pos, k_pos, causal, cfg.window)
        logits = logits + bias[None, None]
        w = jax.nn.softmax(logits, axis=-1).astype(compute_dtype)
        o = jnp.einsum("bhst,bthd->bshd", w, v).reshape(B, S, H * hd)
    o = shard(o, sh.dp, None, sh.tp)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(compute_dtype))
    out = shard(out, sh.dp, None, None)
    if return_kv:
        return out, (k0, v0)
    return out


# ---------------------------------------------------------------------------
# decode with KV cache
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    """Per-layer-stack KV cache.

    k, v: (L, B, C, K, hd) where C = cache length (= min(window, seq) for
    windowed layers — a RING buffer indexed mod C — else seq).
    """

    k: jnp.ndarray
    v: jnp.ndarray

    @property
    def length(self) -> int:
        return self.k.shape[2]


def make_cache(L: int, B: int, C: int, cfg: AttnConfig, dtype) -> KVCache:
    K, hd = cfg.n_kv_heads, cfg.head_dim
    return KVCache(
        k=jnp.zeros((L, B, C, K, hd), dtype),
        v=jnp.zeros((L, B, C, K, hd), dtype),
    )


def decode_attention_shardmap(p, x, cache_k, cache_v, pos, cfg: AttnConfig,
                              sh: ShardPlan, compute_dtype):
    """Flash-decoding via shard_map (§Perf optimized variant).

    The GSPMD path updates a sequence-sharded cache with a dynamic-index
    DUS, which the SPMD partitioner handles by REPLICATING the whole
    cache ("involuntary full rematerialization") — reading and writing
    O(cache) bytes per token.  Here the cache stays sharded over the TP
    axis on the sequence dim and each rank:

      1. locally writes the new KV iff it owns slot ``pos`` (no comm);
      2. computes attention over ITS seq shard with a local max/sum;
      3. merges across ranks with one tiny LSE psum (flash-decoding).

    Wire bytes per layer-step: O(B * H * hd) for the merge — independent
    of the cache length.  Falls back to None when no mesh is active.
    """
    from jax.sharding import PartitionSpec as P

    m = _active_mesh()
    if m is None or cfg.window is not None:
        return None
    tp = sh.tp
    dp = tuple(a for a in (sh.dp if isinstance(sh.dp, (tuple, list))
                           else (sh.dp,)) if a in m.axis_names)
    if tp not in m.axis_names:
        return None
    tp_size = m.shape[tp]
    B, _, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    C = cache_k.shape[1]
    if C % tp_size:
        return None
    C_loc = C // tp_size
    batch_sharded = bool(dp) and B % (max(1, _axes_size(m, dp))) == 0 and B >= 16
    bspec = dp if batch_sharded else None

    def local_fn(pl, xl, ck, cv, pos_):
        rank = jax.lax.axis_index(tp)
        Bl = xl.shape[0]
        xc = xl.astype(compute_dtype)
        q = jnp.einsum("bsd,dh->bsh", xc, pl["wq"].astype(compute_dtype)
                       ).reshape(Bl, 1, H, hd)
        k = jnp.einsum("bsd,dh->bsh", xc, pl["wk"].astype(compute_dtype)
                       ).reshape(Bl, 1, K, hd)
        v = jnp.einsum("bsd,dh->bsh", xc, pl["wv"].astype(compute_dtype)
                       ).reshape(Bl, 1, K, hd)
        if cfg.qk_norm:
            q = rms_norm(q, pl["q_norm"])
            k = rms_norm(k, pl["k_norm"])
        if cfg.rope_fraction > 0:
            pvec = jnp.full((1,), pos_, jnp.int32)
            q = rope(q, pvec, cfg.rope_theta, cfg.rope_fraction)
            k = rope(k, pvec, cfg.rope_theta, cfg.rope_fraction)

        # 1. local ring write: only the owner rank mutates its shard.
        owner = pos_ // C_loc
        mine = rank == owner
        slot = jnp.where(mine, pos_ % C_loc, 0)
        cur_k = jax.lax.dynamic_slice(ck, (0, slot, 0, 0), (Bl, 1, K, hd))
        cur_v = jax.lax.dynamic_slice(cv, (0, slot, 0, 0), (Bl, 1, K, hd))
        wk_ = jnp.where(mine, k.astype(ck.dtype), cur_k)
        wv_ = jnp.where(mine, v.astype(cv.dtype), cur_v)
        ck = jax.lax.dynamic_update_slice(ck, wk_, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, wv_, (0, slot, 0, 0))

        # 2. local attention over this rank's seq shard.
        base = rank * C_loc
        idx = base + jnp.arange(C_loc, dtype=jnp.int32)
        valid = idx <= pos_
        G = H // K
        qg = q.reshape(Bl, K, G, hd)
        logits = jnp.einsum("bkgh,btkh->bkgt", qg,
                            ck.astype(compute_dtype)).astype(jnp.float32)
        logits = logits / jnp.sqrt(hd).astype(jnp.float32)
        logits = softcap(logits, cfg.softcap)
        logits = jnp.where(valid[None, None, None, :], logits, -jnp.inf)
        m_loc = jnp.max(logits, axis=-1)                      # (B,K,G)
        m_glob = jax.lax.pmax(m_loc, tp)
        m_safe = jnp.where(jnp.isfinite(m_glob), m_glob, 0.0)
        p_ = jnp.exp(logits - m_safe[..., None])
        p_ = jnp.where(valid[None, None, None, :], p_, 0.0)
        l_loc = jnp.sum(p_, axis=-1)                          # (B,K,G)
        o_loc = jnp.einsum("bkgt,btkh->bkgh", p_.astype(compute_dtype),
                           cv.astype(compute_dtype)).astype(jnp.float32)
        # 3. one LSE merge: psum of (l, o) — O(B*H*hd) wire bytes.
        l_glob = jax.lax.psum(l_loc, tp)
        o_glob = jax.lax.psum(o_loc, tp)
        o = (o_glob / jnp.maximum(l_glob, 1e-30)[..., None])
        o = o.reshape(Bl, 1, H * hd).astype(compute_dtype)
        out = jnp.einsum("bsh,hd->bsd", o, pl["wo"].astype(compute_dtype))
        return out, ck, cv

    pspec = {k_: P(None, None) for k_ in ("wq", "wk", "wv", "wo")}
    if cfg.qk_norm:
        pspec["q_norm"] = P(None)
        pspec["k_norm"] = P(None)
    cache_spec = P(bspec, tp, None, None)
    fn = jax.shard_map(
        local_fn, mesh=m,
        in_specs=(pspec, P(bspec, None, None), cache_spec, cache_spec, P()),
        out_specs=(P(bspec, None, None), cache_spec, cache_spec),
        check_vma=False)
    return fn(p, x, cache_k, cache_v, pos)


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def decode_attention(p: Pytree, x: jnp.ndarray, cache_k: jnp.ndarray,
                     cache_v: jnp.ndarray, pos: jnp.ndarray, cfg: AttnConfig,
                     sh: ShardPlan, compute_dtype,
                     seq_shard: bool = False):
    """One-token decode for a single layer.

    x: (B, 1, D); cache_k/v: (B, C, K, hd); pos: scalar int32 — the absolute
    position of the new token.  For windowed layers the cache is a ring
    (C == window) written at ``pos % C``; otherwise linear (C == max seq).

    seq_shard: constrain the cache's sequence dim over the TP axis
    (sequence parallelism for long-context decode; GSPMD turns the softmax
    reduction into a psum — flash-decoding-style partial-max merging is the
    §Perf optimized variant via shard_map).

    Returns (out (B,1,D), new_cache_k, new_cache_v).
    """
    B, _, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    C = cache_k.shape[1]

    xc = x.astype(compute_dtype)
    q = jnp.einsum("bsd,dh->bsh", xc, p["wq"].astype(compute_dtype)).reshape(B, 1, H, hd)
    k = jnp.einsum("bsd,dh->bsh", xc, p["wk"].astype(compute_dtype)).reshape(B, 1, K, hd)
    v = jnp.einsum("bsd,dh->bsh", xc, p["wv"].astype(compute_dtype)).reshape(B, 1, K, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if cfg.rope_fraction > 0:
        pvec = jnp.full((1,), pos, jnp.int32)
        q = rope(q, pvec, cfg.rope_theta, cfg.rope_fraction)
        k = rope(k, pvec, cfg.rope_theta, cfg.rope_fraction)

    slot = jnp.where(cfg.window is not None, pos % C, jnp.minimum(pos, C - 1))
    cache_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                           (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                           (0, slot, 0, 0))
    if seq_shard:
        sp = tuple(sh.dp) + (sh.tp,)
        cache_k = shard(cache_k, None, sp, None, None)
        cache_v = shard(cache_v, None, sp, None, None)
    else:
        cache_k = shard(cache_k, sh.dp, None, None, None)
        cache_v = shard(cache_v, sh.dp, None, None, None)

    # Validity of cache slots: ring ⇒ last `window` positions; linear ⇒ <= pos.
    idx = jnp.arange(C, dtype=jnp.int32)
    if cfg.window is not None:
        # slot i holds absolute position: the ring wraps every C steps.
        age = (slot - idx) % C           # 0 == newest
        valid = age <= jnp.minimum(pos, C - 1)
    else:
        valid = idx <= pos

    G = H // K
    qg = q.reshape(B, K, G, hd)
    kc = cache_k.astype(compute_dtype)
    vc = cache_v.astype(compute_dtype)
    logits = jnp.einsum("bkgh,btkh->bkgt", qg, kc).astype(jnp.float32)
    logits = logits / jnp.sqrt(hd).astype(jnp.float32)
    logits = softcap(logits, cfg.softcap)
    logits = jnp.where(valid[None, None, None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1).astype(compute_dtype)
    o = jnp.einsum("bkgt,btkh->bkgh", w, vc).reshape(B, 1, H * hd)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(compute_dtype))
    return out, cache_k, cache_v
