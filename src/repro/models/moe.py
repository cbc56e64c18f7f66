"""Mixture-of-Experts with BULK-STEAL token rebalancing.

This is the paper's technique applied inside the model: after top-k
routing, each expert is a "worker" whose queue is its assigned token
batch.  Experts past ``capacity`` would normally drop their overflow
(GShard).  Here a *virtual master* — one deterministic, replicated pass,
exactly like ``core.master`` — bulk-steals the overflow suffix and
reassigns it to the experts with slack:

  1. routing = bulk push: positions within each expert come from one
     vectorized cumsum (constant per-token cost — the paper's flat-latency
     bulk push).
  2. overflow detection = the ``_queue_limit_``/capacity guard.
  3. reassignment = proportional bulk steal: the k-th overflow token goes
     to the k-th unit of cross-expert slack (computed by one searchsorted
     over the cumulative-slack vector — a single "cut" per expert, the
     linearization-point analogue).

The result is *dropless* MoE with a deterministic O(T log T) plan and no
per-token synchronization.  ``moe_bulk_steal=False`` gives the GShard
drop baseline for ablations (paper-faithful "no steal" comparison).

Expert compute is grouped matmuls on (E, C, D) buffers: EP-sharded over
the TP axis when E % tp == 0 (qwen3: 128 experts), else capacity-sharded
with TP inside each expert (mixtral: 8 experts on tp=16).
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import ShardPlan, dense_init, shard, pscan

Pytree = Any

__all__ = ["moe_init", "moe_apply", "route_with_bulk_steal"]


def moe_init(key, L: int, d_model: int, n_experts: int, d_ff_e: int, dtype) -> Pytree:
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (L, d_model, n_experts), dtype),
        "w_gate": dense_init(ks[1], (L, n_experts, d_model, d_ff_e), dtype),
        "w_up": dense_init(ks[2], (L, n_experts, d_model, d_ff_e), dtype),
        "w_down": dense_init(ks[3], (L, n_experts, d_ff_e, d_model), dtype),
    }


# ---------------------------------------------------------------------------
# routing with bulk-steal rebalancing
# ---------------------------------------------------------------------------


def route_with_bulk_steal(
    probs: jnp.ndarray,      # (T, E) router softmax
    top_k: int,
    capacity: int,
    bulk_steal: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compute (expert, slot, weight, valid) for each of T*top_k assignments.

    Returns flat arrays of shape (T*top_k,):
      expert: expert id per assignment (possibly re-routed by the steal)
      slot:   position within the expert's capacity buffer
      weight: combine weight (router prob, renormalized per token)
      valid:  assignment lands in a real slot (always true for stolen
              tokens when total slack suffices; false only when the whole
              system is over capacity)
    """
    T, E = probs.shape
    w, experts = jax.lax.top_k(probs, top_k)              # (T, k)
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)

    flat_e = experts.reshape(-1)                          # (A,) A = T*k
    flat_w = w.reshape(-1)
    A = flat_e.shape[0]

    # --- bulk push: slot = rank of this assignment within its expert -------
    # Sort-based ranking: O(A log A) and O(A) memory (a (A, E) one-hot
    # cumsum would replicate multi-GB intermediates at the assigned scale).
    order = jnp.argsort(flat_e, stable=True)              # (A,)
    inv = jnp.zeros((A,), jnp.int32).at[order].set(
        jnp.arange(A, dtype=jnp.int32))
    sorted_e = flat_e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=jnp.int32),
                             side="left").astype(jnp.int32)
    end = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=jnp.int32),
                           side="right").astype(jnp.int32)
    slot = inv - start[flat_e]                            # rank within expert
    load = end - start                                    # (E,) expert loads

    overflow = slot >= capacity
    if not bulk_steal:
        return flat_e, jnp.minimum(slot, capacity - 1), flat_w, ~overflow

    # --- proportional bulk steal of the overflow suffix ---------------------
    # Slack per expert and its cumulative vector: one searchsorted maps the
    # j-th overflow assignment to the expert owning the j-th slack unit.
    slack = jnp.maximum(capacity - load, 0)               # (E,)
    cum_slack = jnp.cumsum(slack)                         # (E,)
    total_slack = cum_slack[-1]

    # Rank the overflow assignments (stable order = routing order).
    ovf_rank = jnp.cumsum(overflow.astype(jnp.int32)) - overflow.astype(jnp.int32)
    thief = jnp.searchsorted(cum_slack, ovf_rank, side="right").astype(jnp.int32)
    thief = jnp.minimum(thief, E - 1)
    # Slot within the thief = base load + index within that thief's block.
    prev_cum = jnp.where(thief > 0, cum_slack[jnp.maximum(thief - 1, 0)], 0)
    thief_slot = load[thief] + (ovf_rank - prev_cum)

    stolen_ok = overflow & (ovf_rank < total_slack)
    new_e = jnp.where(stolen_ok, thief, flat_e)
    new_slot = jnp.where(stolen_ok, thief_slot, slot)
    # Stolen tokens keep their router weight for the ORIGINAL expert: the
    # thief computes on their behalf (the master moved the task, not the
    # objective) — mirrors redistributed solver nodes keeping their bounds.
    valid = (~overflow) | stolen_ok
    new_slot = jnp.clip(new_slot, 0, capacity - 1)
    return new_e, new_slot, flat_w, valid


# Token-chunk size for the dispatch pipeline: the (E, C, D) buffers and
# routing tensors scale with the chunk, not the full 1M-token batch, so
# per-device transients stay ~100s of MB at the assigned shapes.
MOE_CHUNK_TOKENS = 65_536


def _moe_chunk(p, xt, *, top_k, n_experts, capacity_factor, sh,
               compute_dtype, bulk_steal, ep):
    """MoE for one (Tc, D) token chunk."""
    Tc, D = xt.shape
    E = n_experts
    logits = jnp.einsum("td,de->te", xt, p["router"].astype(compute_dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    capacity = int(max(Tc * top_k / E * capacity_factor, top_k))
    capacity = -(-capacity // 8) * 8  # round up to 8 for clean layouts
    expert, slot, weight, valid = route_with_bulk_steal(
        probs, top_k, capacity, bulk_steal=bulk_steal)

    tok = jnp.repeat(jnp.arange(Tc, dtype=jnp.int32), top_k)

    # Dispatch: scatter token vectors into the (E, C, D) expert buffers.
    flat_idx = jnp.where(valid, expert * capacity + slot, E * capacity)
    buf = jnp.zeros((E * capacity, D), compute_dtype)
    buf = buf.at[flat_idx].set(xt[tok], mode="drop")
    buf = buf.reshape(E, capacity, D)
    buf = shard(buf, sh.tp if ep else None, None if ep else sh.tp, None)

    # Expert compute: grouped SwiGLU matmuls (EP over experts when the
    # expert count divides the TP axis, else TP inside each expert).
    wg = p["w_gate"].astype(compute_dtype)
    wu = p["w_up"].astype(compute_dtype)
    wd = p["w_down"].astype(compute_dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
    h = shard(h, sh.tp if ep else None, None if ep else sh.tp, None)
    out_buf = jnp.einsum("ecf,efd->ecd", h, wd).reshape(E * capacity, D)

    # Combine: gather back and weight.
    gathered = out_buf[jnp.minimum(flat_idx, E * capacity - 1)]
    gathered = gathered * (weight * valid.astype(jnp.float32)).astype(compute_dtype)[:, None]
    out = jnp.zeros((Tc, D), compute_dtype).at[tok].add(gathered)
    return out


def moe_apply(p: Pytree, x: jnp.ndarray, *, top_k: int, n_experts: int,
              capacity_factor: float, sh: ShardPlan, compute_dtype,
              bulk_steal: bool = True, impl: str = "gspmd") -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D). p leaves are per-layer (no L dim).

    Tokens are processed in MOE_CHUNK_TOKENS chunks via lax.scan — a
    dispatch PIPELINE that bounds routing/buffer transients (the steal
    rebalancing scope is the chunk).  One chunk == one bulk push+steal
    round of the paper's model.

    impl="gspmd": auto-partitioned dispatch (baseline — GSPMD turns the
    token->expert scatter into large all-gathers).
    impl="ep_shardmap": explicit expert parallelism (see
    moe_apply_ep_shardmap) — beyond-paper §Perf optimization.
    """
    if impl == "ep_shardmap":
        out = moe_apply_ep_shardmap(
            p, x, top_k=top_k, n_experts=n_experts,
            capacity_factor=capacity_factor, sh=sh,
            compute_dtype=compute_dtype, bulk_steal=bulk_steal)
        if out is not None:
            return out
        # fall through to gspmd when no mesh / experts don't divide tp

    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D).astype(compute_dtype)
    tp = _tp_size(sh)
    ep = (n_experts % tp == 0) if tp else True

    kw = dict(top_k=top_k, n_experts=n_experts,
              capacity_factor=capacity_factor, sh=sh,
              compute_dtype=compute_dtype, bulk_steal=bulk_steal, ep=ep)

    if T <= MOE_CHUNK_TOKENS:
        out = _moe_chunk(p, xt, **kw)
        return shard(out.reshape(B, S, D), sh.dp, None, None)

    nc = -(-T // MOE_CHUNK_TOKENS)
    while T % nc:
        nc += 1
    xc = xt.reshape(nc, T // nc, D)

    def step(_, xchunk):
        return None, _moe_chunk(p, xchunk, **kw)

    _, out = pscan(step, None, xc)
    out = out.reshape(T, D)
    return shard(out.reshape(B, S, D), sh.dp, None, None)


# ---------------------------------------------------------------------------
# Optimized expert-parallel dispatch (beyond-paper, §Perf)
# ---------------------------------------------------------------------------


def moe_apply_ep_shardmap(p: Pytree, x: jnp.ndarray, *, top_k: int,
                          n_experts: int, capacity_factor: float,
                          sh: ShardPlan, compute_dtype,
                          bulk_steal: bool = True):
    """Explicit EP via shard_map: activations are REPLICATED over the TP
    axis in this framework's layout, so each TP rank can (a) run the
    identical routing plan, (b) LOCALLY gather the tokens assigned to its
    own E/tp experts (zero dispatch collectives — the GSPMD baseline
    all-gathers hundreds of GB here), (c) compute its grouped matmuls,
    and (d) combine with ONE psum over tp.  Per-device wire bytes drop
    from O(T*D) gathers to one (T_loc, D) all-reduce per chunk.

    Returns None when unavailable (no mesh, or E % tp != 0).
    """
    from jax.sharding import PartitionSpec as P
    from repro.models.layers import _active_mesh

    mesh = _active_mesh()
    if mesh is None or sh.tp not in mesh.axis_names:
        return None
    tp_size = mesh.shape[sh.tp]
    if n_experts % tp_size != 0:
        return None
    dp_axes = tuple(a for a in
                    (sh.dp if isinstance(sh.dp, (tuple, list)) else (sh.dp,))
                    if a in mesh.axis_names)
    B, S, D = x.shape
    Eo = n_experts // tp_size  # experts per rank

    def local_fn(pl, xl):
        rank = jax.lax.axis_index(sh.tp)
        Bl, Sl, _ = xl.shape
        Tl = Bl * Sl
        xt = xl.reshape(Tl, D).astype(compute_dtype)

        def chunk(xt_c):
            Tc = xt_c.shape[0]
            logits = jnp.einsum("td,de->te", xt_c,
                                pl["router"].astype(compute_dtype))
            probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
            capacity = int(max(Tc * top_k / n_experts * capacity_factor,
                               top_k))
            capacity = -(-capacity // 8) * 8
            expert, slot, weight, valid = route_with_bulk_steal(
                probs, top_k, capacity, bulk_steal=bulk_steal)
            tok = jnp.repeat(jnp.arange(Tc, dtype=jnp.int32), top_k)
            # keep only assignments owned by this rank's experts
            mine = valid & (expert // Eo == rank)
            local_e = expert % Eo
            flat_idx = jnp.where(mine, local_e * capacity + slot,
                                 Eo * capacity)
            buf = jnp.zeros((Eo * capacity, D), compute_dtype)
            buf = buf.at[flat_idx].set(xt_c[tok], mode="drop")
            buf = buf.reshape(Eo, capacity, D)
            wg = pl["w_gate"].astype(compute_dtype)
            wu = pl["w_up"].astype(compute_dtype)
            wd = pl["w_down"].astype(compute_dtype)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
            h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
            ob = jnp.einsum("ecf,efd->ecd", h, wd).reshape(Eo * capacity, D)
            g = ob[jnp.minimum(flat_idx, Eo * capacity - 1)]
            g = g * (weight * mine.astype(jnp.float32)
                     ).astype(compute_dtype)[:, None]
            out = jnp.zeros((Tc, D), compute_dtype).at[tok].add(g)
            # ONE combine collective: sum each rank's expert contributions
            return jax.lax.psum(out, sh.tp)

        if Tl <= MOE_CHUNK_TOKENS:
            out = chunk(xt)
        else:
            nc = -(-Tl // MOE_CHUNK_TOKENS)
            while Tl % nc:
                nc += 1
            _, out = pscan(lambda c, xc: (None, chunk(xc)), None,
                           xt.reshape(nc, Tl // nc, D))
            out = out.reshape(Tl, D)
        return out.reshape(Bl, Sl, D)

    pspec = {
        "router": P(None, None),
        "w_gate": P(sh.tp, None, None),
        "w_up": P(sh.tp, None, None),
        "w_down": P(sh.tp, None, None),
    }
    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(pspec, P(dp_axes or None, None, None)),
                       out_specs=P(dp_axes or None, None, None),
                       check_vma=False)
    return fn(p, x.astype(compute_dtype))


def _tp_size(sh: ShardPlan) -> int:
    from repro.models.layers import _active_mesh

    m = _active_mesh()
    if m is None or sh.tp not in m.axis_names:
        return 0
    return m.shape[sh.tp]
