"""Continuous-batching decode: real model execution as a steal workload.

This replaces the wave engine's toy worker body with a decode-step state
machine that runs INSIDE the executor round, under
:func:`repro.runtime.executor.make_lane_step` — so the identical traced
body serves all three execution modes (host-mastered vmap lanes,
device-mastered vmap lanes, one-lane-per-device ``shard_map``).

Each lane owns:

* a ring of QUEUED requests (full prompt payloads — KV-free prefill
  work, which the superstep's bulk steal moves freely between lanes:
  Castañeda & Piña's multiplicity argument licenses this fence-free);
* ``n_slots`` decode SLOTS — in-flight sequences, each holding its
  position, token budget and a page-table row into the lane's paged KV
  pool (:mod:`repro.serve.paged_kv`);
* an OUTPUT ring of finished-request records the host harvests after
  every round.

One round per lane = continuous batching in miniature: bulk-pop as many
queued requests as there are free slots, allocate KV pages (slots stall
under page pressure instead of erroring), advance EVERY active slot by
one token — prompt tokens are teacher-forced one at a time, so prefill
and decode are the same per-slot step and sequences at different phases
batch together — then retire finished sequences, pushing their output
record and freeing their pages in the SAME round their slot reopens.
Per-item cost is genuinely irregular (prompt lengths and sampled output
lengths differ per request), which is the regime the paper's closing
argument claims amplifies bulk stealing.

Per-request greedy tokens depend only on (params, prompt, budget) —
slot assignment, stalls and steals change WHEN a token is produced,
never its value — so the served-token multiset is schedule-invariant
and identical across execution modes (the acceptance gate
``benchmarks/serve_decode.py`` asserts).

Timestamps (admit / first token / finish) are stamped in LOGICAL rounds
— the lane-local round counter all modes advance identically — and
flow into :class:`repro.runtime.telemetry.Telemetry` as
:class:`~repro.runtime.telemetry.RequestRecord` SLO percentiles.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ops as bulk_ops
from repro.core.policy import StealPolicy, plan_transfers
from repro.runtime.adaptive import AdaptiveConfig, AdaptiveController
from repro.serve import paged_kv
from repro.serve.scheduler import Request
from repro.train.fault import StragglerMonitor

Pytree = Any
_tmap = jax.tree_util.tree_map

__all__ = ["DecodePolicy", "DecodeCluster", "request_spec", "output_spec",
           "encode_requests", "init_decode_state", "make_decode_body"]

_NOOP_WATERMARK = 2 ** 30 - 1


@dataclasses.dataclass(frozen=True)
class DecodePolicy:
    """Geometry + steal knobs of the decode subsystem (per lane).

    Attributes:
      n_slots: concurrent in-flight sequences per lane.
      max_prompt / max_new: static per-request bounds (ring item payload
        is ``max_prompt + 4`` int32s; the KV budget per sequence is
        ``max_prompt + max_new`` rows).
      page_size: KV rows per page.
      n_pages: physical pages per lane pool.  ``None`` sizes the pool so
        every slot can always complete (no page pressure); smaller
        values make page pressure a real scheduling signal (slots
        stall until a retirement frees pages).
      out_capacity: finished-record ring size (must cover retirements
        between host harvests; the cluster harvests every round).
      steal: what a steal may move — ``"queue"`` (the cheap path: only
        KV-free queued prefill items ride the superstep exchange) or
        ``"migrate"`` (additionally, the master may move one in-flight
        request per round between lanes, pages and all, when token
        loads diverge past ``migrate_threshold``).
      migrate_threshold: max/min token-load ratio that triggers a
        migration under ``steal="migrate"``.
      load_low / load_high: token-load watermarks for the adaptive
        steal-proportion controller (the decode analogue of the item
        watermarks — ``None`` derives them from one request's worth of
        tokens).
    """

    n_slots: int = 4
    max_prompt: int = 16
    max_new: int = 16
    page_size: int = 8
    n_pages: Optional[int] = None
    out_capacity: Optional[int] = None
    steal: str = "queue"
    migrate_threshold: float = 1.5
    load_low: Optional[int] = None
    load_high: Optional[int] = None

    def __post_init__(self):
        if self.steal not in ("queue", "migrate"):
            raise ValueError(f"steal must be 'queue' or 'migrate', got "
                             f"{self.steal!r}")

    @property
    def pages_per_seq(self) -> int:
        return paged_kv.pages_for(self.max_prompt + self.max_new,
                                  self.page_size)

    @property
    def pool_pages(self) -> int:
        return (self.n_pages if self.n_pages is not None
                else self.n_slots * self.pages_per_seq)

    @property
    def out_ring(self) -> int:
        return (self.out_capacity if self.out_capacity is not None
                else 4 * self.n_slots)

    @property
    def token_low(self) -> int:
        return (self.load_low if self.load_low is not None
                else self.max_prompt + self.max_new)

    @property
    def token_high(self) -> int:
        return (self.load_high if self.load_high is not None
                else 3 * (self.max_prompt + self.max_new))


def request_spec(policy: DecodePolicy) -> Dict[str, jax.ShapeDtypeStruct]:
    """Queue item: one admitted (prefill-pending, KV-free) request."""
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    return {"rid": i32(), "plen": i32(), "max_new": i32(), "admit": i32(),
            "prompt": i32(policy.max_prompt)}


def output_spec(policy: DecodePolicy) -> Dict[str, jax.ShapeDtypeStruct]:
    """Output-ring item: one finished request's tokens + SLO stamps."""
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    return {"rid": i32(), "n": i32(), "admit": i32(), "first": i32(),
            "finish": i32(), "toks": i32(policy.max_new)}


def encode_requests(requests: Sequence[Request], policy: DecodePolicy,
                    admit_round: int) -> Dict[str, jnp.ndarray]:
    """Pad a request batch into the queue-item layout (rows = len)."""
    n = len(requests)
    prompt = np.zeros((n, policy.max_prompt), np.int32)
    plen = np.zeros((n,), np.int32)
    maxn = np.zeros((n,), np.int32)
    rid = np.zeros((n,), np.int32)
    for i, r in enumerate(requests):
        p = list(r.prompt)
        if not 0 < len(p) <= policy.max_prompt:
            raise ValueError(
                f"request {r.rid}: prompt length {len(p)} outside "
                f"(0, {policy.max_prompt}]")
        if not 0 < r.max_new <= policy.max_new:
            raise ValueError(
                f"request {r.rid}: max_new {r.max_new} outside "
                f"(0, {policy.max_new}]")
        prompt[i, : len(p)] = p
        plen[i] = len(p)
        maxn[i] = r.max_new
        rid[i] = r.rid
    return {"rid": jnp.asarray(rid), "plen": jnp.asarray(plen),
            "max_new": jnp.asarray(maxn),
            "admit": jnp.full((n,), jnp.int32(admit_round)),
            "prompt": jnp.asarray(prompt)}


# ---------------------------------------------------------------------------
# Per-lane decode state
# ---------------------------------------------------------------------------


def init_decode_state(model, policy: DecodePolicy, n_lanes: int) -> Pytree:
    """The stacked ``(n_lanes, ...)`` decode carry: slot arrays, the
    paged KV pool and the finished-record output ring, per lane."""
    S, MP, MN = policy.n_slots, policy.max_prompt, policy.max_new
    pool = paged_kv.make_pool(model, n_slots=S, n_pages=policy.pool_pages,
                              page_size=policy.page_size,
                              pages_per_seq=policy.pages_per_seq)
    z = lambda *s: jnp.zeros(s, jnp.int32)
    lane = {
        "pages": pool["pages"], "table": pool["table"],
        "owner": pool["owner"], "n_alloc": z(S),
        "active": jnp.zeros((S,), jnp.bool_),
        "pos": z(S), "plen": z(S), "maxn": z(S),
        "rid": jnp.full((S,), jnp.int32(-1)), "admit": z(S),
        "first": jnp.full((S,), jnp.int32(-1)), "cur": z(S),
        "prompt": z(S, MP), "toks": z(S, MN),
        "round": z(), "stalls": z(), "dropped": z(), "load": z(),
        "out_q": bulk_ops.make_queue(policy.out_ring, output_spec(policy)),
    }
    return _tmap(lambda x: jnp.tile(x[None], (n_lanes,) + (1,) * x.ndim),
                 lane)


def make_decode_body(model, params, policy: DecodePolicy,
                     ops_in: bulk_ops.BulkOps, ops_out: bulk_ops.BulkOps):
    """The decode worker body ``(q, state) -> (q, state)`` for ONE lane.

    Pure traced jnp over the lane's queue ring + decode state; runs
    unmodified under ``jax.vmap`` lanes and per-device ``shard_map``
    (no collectives — the rebalancing superstep that follows it inside
    :func:`~repro.runtime.executor.make_lane_step` has those).
    """
    S, MP, MN, PS = (policy.n_slots, policy.max_prompt, policy.max_new,
                     policy.page_size)
    n_pages = policy.pool_pages
    step_fn = jax.vmap(lambda cache, tok: model.decode_step(
        params, cache, tok))

    PP = policy.pages_per_seq

    def body(q, st):
        r = st["round"]
        active = st["active"]
        # -- continuous admission: bulk-pop one request per free slot,
        # bounded by the page RESERVATION budget.  Every active slot
        # holds a reservation for its full sequence (pages_for(plen +
        # max_new)); a request is only seated while the pool can still
        # cover a worst-case newcomer.  The invariant "sum of active
        # reservations <= n_pages" makes allocation failure transient
        # (a needing slot always finds its reserved page free), so page
        # pressure back-pressures ADMISSION instead of deadlocking
        # seated sequences.
        n_free = jnp.sum((~active).astype(jnp.int32))
        pf = (st["plen"] + st["maxn"] + PS - 1) // PS
        committed = jnp.sum(jnp.where(active, pf, 0))
        budget = jnp.maximum(n_pages - committed, 0) // PP
        n_admit = jnp.minimum(n_free, budget)
        blocked = jnp.maximum(jnp.minimum(n_free, q.size) - n_admit, 0)
        q, batch, n_pop = ops_in.pop_bulk(q, S, n_admit)
        order = jnp.argsort(active)            # free slots first (stable)
        take = jnp.arange(S, dtype=jnp.int32) < n_pop

        def seat(cur_arr, new_rows):
            sel = take.reshape((S,) + (1,) * (new_rows.ndim - 1))
            vals = jnp.where(sel, new_rows, cur_arr[order])
            return cur_arr.at[order].set(vals)

        st = dict(st)
        z = jnp.zeros((S,), jnp.int32)
        st["rid"] = seat(st["rid"], batch["rid"])
        st["plen"] = seat(st["plen"], batch["plen"])
        st["maxn"] = seat(st["maxn"], batch["max_new"])
        st["admit"] = seat(st["admit"], batch["admit"])
        st["prompt"] = seat(st["prompt"], batch["prompt"])
        st["pos"] = seat(st["pos"], z)
        st["cur"] = seat(st["cur"], z)
        st["first"] = seat(st["first"], z - 1)
        st["toks"] = seat(st["toks"], jnp.zeros((S, MN), jnp.int32))
        active = seat(active, jnp.ones((S,), jnp.bool_))
        st["active"] = active

        # -- page allocation; slots stall under page pressure ----------
        pos = st["pos"]
        need = active & (pos // PS >= st["n_alloc"])
        table, owner, n_alloc = paged_kv.alloc_pages(
            st["table"], st["owner"], st["n_alloc"], need, pos // PS)
        advance = active & (pos // PS < n_alloc)
        # Stalls = free slots the page budget refused to fill while
        # requests were queued (admission back-pressure) + seated slots
        # whose page grant was deferred a round (transient only, by the
        # reservation invariant above).
        st["stalls"] = (st["stalls"] + blocked
                        + jnp.sum((active & ~advance).astype(jnp.int32)))

        # -- one decode step for every slot (prompt teacher-forced) ----
        cache_in = paged_kv.gather_slot_caches(st["pages"], table, pos)
        pp = st["prompt"][jnp.arange(S), jnp.clip(pos, 0, MP - 1)]
        feed = jnp.where(pos < st["plen"], pp, st["cur"])
        logits, cache_out = step_fn(cache_in, feed[:, None, None])
        nxt = jnp.argmax(logits[:, 0, 0, :], axis=-1).astype(jnp.int32)

        gidx = pos + 1 - st["plen"]            # generated-token index
        valid_gen = advance & (gidx >= 0) & (gidx < MN)
        srow = jnp.where(valid_gen, jnp.arange(S, dtype=jnp.int32),
                         jnp.int32(S))
        st["toks"] = st["toks"].at[
            srow, jnp.clip(gidx, 0, MN - 1)].set(nxt, mode="drop")
        st["first"] = jnp.where(advance & (gidx == 0), r, st["first"])
        st["cur"] = jnp.where(advance, nxt, st["cur"])
        pos = pos + advance.astype(jnp.int32)
        st["pos"] = pos
        st["pages"] = paged_kv.scatter_slot_caches(
            st["pages"], table,
            {g: cache_in[g] for g in cache_in if g != "pos"},
            {g: cache_out[g] for g in cache_out if g != "pos"},
            advance)

        # -- retire finished sequences; free pages the same round ------
        fin = active & (pos - st["plen"] >= st["maxn"])
        n_fin = jnp.sum(fin.astype(jnp.int32))
        ordf = jnp.argsort(~fin)               # finished slots first
        rec = {"rid": st["rid"][ordf], "n": st["maxn"][ordf],
               "admit": st["admit"][ordf], "first": st["first"][ordf],
               "finish": jnp.full((S,), r), "toks": st["toks"][ordf]}
        out_q, pushed = ops_out.push(st["out_q"], rec, n_fin)
        st["out_q"] = out_q
        st["dropped"] = st["dropped"] + (n_fin - pushed)
        table, owner, n_alloc = paged_kv.free_pages(table, owner, n_alloc,
                                                    fin)
        st["table"], st["owner"], st["n_alloc"] = table, owner, n_alloc
        active = active & ~fin
        st["active"] = active

        # -- true token load: queued work + in-flight remainder --------
        cap = jax.tree_util.tree_leaves(q.buf)[0].shape[0]
        offs = jnp.arange(cap, dtype=jnp.int32)
        live = ((offs - q.lo) % cap) < q.size
        queued = jnp.sum(jnp.where(live, q.buf["plen"] + q.buf["max_new"],
                                   0))
        inflight = jnp.sum(jnp.where(active,
                                     st["plen"] + st["maxn"] - pos, 0))
        st["load"] = (queued + inflight).astype(jnp.int32)
        st["round"] = r + 1
        return q, st

    return body


# ---------------------------------------------------------------------------
# The cluster driver
# ---------------------------------------------------------------------------


class DecodeCluster:
    """N decode lanes + one admission master, in any execution mode.

    ``execution`` selects where the MASTER lives (the decode body is the
    same traced function everywhere):

    * ``"host"`` — the rebalancing plan runs on the host between rounds
      (the :class:`~repro.serve.scheduler.AdmissionMaster` discipline:
      ``plan_transfers`` on queue sizes, owner-side ``steal_exact`` +
      bulk push per pair), the in-trace superstep is a no-op;
    * ``"vmap"`` / ``"mesh"`` — every round IS a device superstep via
      :class:`repro.distributed.RuntimeAdmissionMaster`: decode body,
      then plan + compact exchange on device (one lane per device under
      ``"mesh"``).

    ``balance=False`` freezes rebalancing entirely (the static baseline
    the benchmark compares against); ``admission`` picks least
    token-load (``"load"``) or static round-robin (``"rr"``) routing.
    The steal proportion is servo'd by an
    :class:`~repro.runtime.adaptive.AdaptiveController` fed TRUE
    per-lane token loads (queued + in-flight tokens, computed in-trace)
    rather than request counts.
    """

    def __init__(self, model, params, *,
                 policy: Optional[DecodePolicy] = None,
                 steal_policy: Optional[StealPolicy] = None,
                 n_lanes: int = 4, capacity: int = 64,
                 execution: str = "vmap",
                 balance: bool = True, admission: str = "load",
                 adaptive: bool = True,
                 adaptive_config: Optional[AdaptiveConfig] = None,
                 mesh=None, backend=None,
                 straggler_threshold: float = 2.0):
        if execution not in ("host", "vmap", "mesh"):
            raise ValueError(f"unknown execution {execution!r}")
        if admission not in ("load", "rr"):
            raise ValueError(f"unknown admission {admission!r}")
        self.model, self.params = model, params
        self.policy = policy or DecodePolicy()
        self.execution = execution
        self.balance = bool(balance)
        self.admission = admission
        self.n_lanes = int(n_lanes)
        # Decode-tuned defaults: queued backlogs are small (slots absorb
        # one request per free seat per round), so even a 2-deep queue
        # next to an idle lane is worth moving.
        spol = steal_policy or StealPolicy(
            proportion=0.5, low_watermark=0, high_watermark=2,
            queue_limit=1, max_steal=min(64, capacity))
        self._steal_policy = spol
        noop = dataclasses.replace(spol, high_watermark=_NOOP_WATERMARK,
                                   queue_limit=_NOOP_WATERMARK)
        # The in-trace superstep rebalances only in device-mastered,
        # balanced mode; host mode (and the static baseline) compiles
        # the no-victim plan, which moves nothing.
        trace_pol = spol if (balance and execution != "host") else noop
        spec = request_spec(self.policy)
        self.master = None
        if execution == "host":
            from repro.runtime.executor import StealRuntime

            self.runtime = StealRuntime(
                self.n_lanes, capacity, spec, policy=trace_pol,
                adaptive=False, max_pop=self.policy.n_slots,
                backend=backend)
        else:
            from repro.distributed.serve import RuntimeAdmissionMaster

            self.master = RuntimeAdmissionMaster(
                self.n_lanes, policy=trace_pol, adaptive=False,
                execution=execution, capacity=capacity, mesh=mesh,
                item_spec=spec, max_pop=self.policy.n_slots,
                elastic=False, backend=backend)
            self.runtime = self.master.runtime
        # Token-load-watermarked proportion servo (decode's analogue of
        # the item-count controller): its output is injected into the
        # compiled round as the traced proportion scalar each step.
        token_pol = dataclasses.replace(
            spol, low_watermark=self.policy.token_low,
            high_watermark=self.policy.token_high)
        self.controller = (AdaptiveController(token_pol, adaptive_config)
                           if (adaptive and self.balance) else None)
        self._ops_out = bulk_ops.make_ops(
            "reference", capacity=self.policy.out_ring,
            max_push=self.policy.n_slots, max_pop=self.policy.out_ring,
            check=False)
        self._worker = make_decode_body(model, params, self.policy,
                                        self.runtime.ops, self._ops_out)
        self.carry = init_decode_state(model, self.policy, self.n_lanes)
        self._requests: Dict[int, Request] = {}
        self.done: List[Request] = []
        self.pending = 0
        self.rounds = 0
        self.stolen = 0
        self.migrated = 0
        self._loads = np.zeros((self.n_lanes,), np.int64)
        self._rr = 0
        self.monitor = StragglerMonitor(threshold=straggler_threshold)

    # -- surface -------------------------------------------------------------

    @property
    def telemetry(self):
        return self.runtime.telemetry

    def note_straggler(self, rounds: int = 4, factor: float = 1.5) -> None:
        """Straggler response: counted in telemetry and, when the token
        controller is on, a temporary steal-proportion boost."""
        self.telemetry.record_fault("straggler")
        if self.controller is not None:
            self.controller.flag_straggler(rounds=rounds, factor=factor)

    # -- admission -----------------------------------------------------------

    def submit(self, requests: Sequence[Request]) -> None:
        """Admit a request batch: ``admission="load"`` routes each
        request greedily to the currently least token-loaded lane
        (updating the estimate as it assigns, so a burst spreads by
        COST); ``admission="rr"`` spreads by COUNT (the static
        baseline).  Either way, one bulk ring push per target lane."""
        requests = list(requests)
        if not requests:
            return
        for r in requests:
            self._requests[r.rid] = r
        groups: Dict[int, List[Request]] = {}
        if self.admission == "load":
            est = self._loads.copy()
            for r in requests:
                lane = int(np.argmin(est))
                est[lane] += len(r.prompt) + r.max_new
                groups.setdefault(lane, []).append(r)
        else:
            for r in requests:
                lane = self._rr % self.n_lanes
                self._rr += 1
                groups.setdefault(lane, []).append(r)
        for lane, reqs in groups.items():
            batch = encode_requests(reqs, self.policy, self.rounds)
            pushed = self.runtime.push(lane, batch, len(reqs))
            if pushed < len(reqs):
                raise RuntimeError(
                    f"admission ring overflow on lane {lane}: pushed "
                    f"{pushed}/{len(reqs)} (capacity "
                    f"{self.runtime.capacity})")
            self._loads[lane] += sum(
                len(r.prompt) + r.max_new for r in reqs)
        self.pending += len(requests)

    # -- host-mastered rebalancing -------------------------------------------

    def _host_rebalance(self) -> int:
        """One host-master round over the device rings: the same
        ``plan_transfers`` pairing the superstep runs, applied by the
        host via owner-side exact steals + bulk pushes."""
        pol = self._steal_policy
        if self.controller is not None:
            pol = dataclasses.replace(
                pol, proportion=self.controller.effective_proportion)
        sizes = self.runtime.sizes()
        plan = np.asarray(plan_transfers(
            jnp.asarray(sizes, jnp.int32), pol))
        ops, qs = self.runtime.ops, self.runtime.queues
        moved = 0
        for thief in range(self.n_lanes):
            src, n = int(plan[thief, 0]), int(plan[thief, 1])
            if n <= 0 or src == thief:
                continue
            qv = _tmap(lambda x: x[src], qs)
            qv, batch, got = ops.steal_exact(qv, jnp.int32(n),
                                             max_steal=pol.max_steal)
            qt = _tmap(lambda x: x[thief], qs)
            qt, pushed = ops.push(qt, batch, got)
            qs = _tmap(lambda full, one: full.at[src].set(one), qs, qv)
            qs = _tmap(lambda full, one: full.at[thief].set(one), qs, qt)
            moved += int(pushed)
        self.runtime.queues = qs
        self.stolen += moved
        return moved

    # -- in-flight migration (steal="migrate") -------------------------------

    def _maybe_migrate(self) -> int:
        """Move ONE in-flight request — slot state, KV pages and all —
        from the most to the least token-loaded lane when their loads
        diverge past ``migrate_threshold``.  Host-side surgery on the
        carry at a round boundary (the only consistency point); page
        content moves bitwise, so the request's remaining tokens are
        unchanged by the move."""
        c = self.carry
        loads = np.asarray(c["load"])
        d, t_lane = int(np.argmax(loads)), int(np.argmin(loads))
        if d == t_lane:
            return 0
        if loads[d] <= self.policy.migrate_threshold * max(loads[t_lane], 1):
            return 0
        active = np.asarray(c["active"])
        plen = np.asarray(c["plen"])
        maxn = np.asarray(c["maxn"])
        pos = np.asarray(c["pos"])
        donor_slots = np.where(active[d])[0]
        free_slots = np.where(~active[t_lane])[0]
        if donor_slots.size == 0 or free_slots.size == 0:
            return 0
        remaining = (plen[d] + maxn[d] - pos[d])[donor_slots]
        s = int(donor_slots[int(np.argmax(remaining))])
        t = int(free_slots[0])
        n_al = int(np.asarray(c["n_alloc"])[d, s])
        owner = np.asarray(c["owner"])
        free_pages = np.where(owner[t_lane] < 0)[0]
        # Preserve the destination's reservation invariant: the moved
        # sequence's FULL page demand must fit next to the active
        # reservations already there, or admission could deadlock.
        PS = self.policy.page_size
        pf = -(-(plen[t_lane] + maxn[t_lane] - 0) // PS)
        committed = int(pf[active[t_lane]].sum())
        seq_pf = -(-(int(plen[d, s]) + int(maxn[d, s])) // PS)
        if committed + seq_pf > self.policy.pool_pages:
            return 0
        if free_pages.size < n_al:
            return 0
        table = np.asarray(c["table"])
        for name in ("rid", "plen", "maxn", "admit", "first", "cur", "pos",
                     "prompt", "toks", "n_alloc"):
            arr = c[name]
            c[name] = arr.at[t_lane, t].set(arr[d, s])
        c["active"] = c["active"].at[t_lane, t].set(True).at[d, s].set(False)
        new_table = c["table"]
        new_owner = c["owner"]
        for j in range(n_al):
            sp = int(table[d, s, j])
            dp = int(free_pages[j])
            for g, kv in c["pages"].items():
                c["pages"][g] = _tmap(
                    lambda x: x.at[t_lane, dp].set(x[d, sp]), kv)
            new_table = new_table.at[t_lane, t, j].set(dp)
            new_owner = new_owner.at[t_lane, dp].set(t)
            new_owner = new_owner.at[d, sp].set(-1)
        trash = self.policy.pool_pages
        new_table = new_table.at[d, s].set(trash)
        c["table"], c["owner"] = new_table, new_owner
        c["n_alloc"] = c["n_alloc"].at[d, s].set(0)
        moved = int(plen[d, s] + maxn[d, s] - pos[d, s])
        self._loads[d] -= moved
        self._loads[t_lane] += moved
        self.migrated += 1
        return 1

    # -- the round -----------------------------------------------------------

    def _harvest(self) -> List[Dict[str, np.ndarray]]:
        """Pop every finished-request record off each lane's output ring
        (host-side, one bulk pop per lane) and clear the rings in the
        carry."""
        ops, c = self._ops_out, self.carry
        cap = self.policy.out_ring
        records = []
        out_q = c["out_q"]
        for i in range(self.n_lanes):
            qi = _tmap(lambda x: x[i], out_q)
            qi, batch, n = ops.pop_bulk(qi, cap, qi.size)
            out_q = _tmap(lambda full, one: full.at[i].set(one), out_q, qi)
            batch = _tmap(np.asarray, batch)
            for j in range(int(n)):
                records.append(_tmap(lambda x: x[j], batch))
        c["out_q"] = out_q
        return records

    def step(self) -> int:
        """One serving tick = one executor round (decode body + exchange
        superstep), then host harvest, SLO accounting, optional
        migration, and the token-load controller update."""
        self.monitor.start()
        if self.controller is not None:
            self.runtime.policy = dataclasses.replace(
                self.runtime.policy,
                proportion=self.controller.effective_proportion)
        before = self.telemetry.total_transferred
        self.carry, _stats = self.runtime.round(self._worker, self.carry)
        self.stolen += self.telemetry.total_transferred - before
        if self.execution == "host" and self.balance:
            self._host_rebalance()
        if int(np.asarray(self.carry["dropped"]).sum()):
            raise RuntimeError(
                "output ring overflow: finished records were dropped — "
                "raise DecodePolicy.out_capacity")
        served, tokens = 0, 0
        for rec in self._harvest():
            n = int(rec["n"])
            self.telemetry.record_request(
                rid=int(rec["rid"]), admit=int(rec["admit"]),
                first=int(rec["first"]), finish=int(rec["finish"]),
                tokens=n)
            req = self._requests.get(int(rec["rid"]))
            if req is not None:
                req.output = [int(x) for x in rec["toks"][:n]]
                self.done.append(req)
            served += 1
            tokens += n
        self.pending -= served
        migrated = 0
        if self.balance and self.policy.steal == "migrate":
            migrated = self._maybe_migrate()
        self._loads = np.asarray(self.carry["load"]).astype(np.int64)
        stragglers = 0
        if self.monitor.observe():
            stragglers = 1
            self.note_straggler()
        if self.controller is not None:
            self.controller.update(self._loads)
        self.telemetry.record_wave(
            loads=self._loads, served=served, tokens=tokens,
            stragglers=stragglers, migrated=migrated)
        self.rounds += 1
        return served

    def run_until_drained(self, max_steps: int = 10_000) -> List[Request]:
        for _ in range(max_steps):
            if self.pending <= 0:
                break
            self.step()
        return self.done

    def stats(self) -> Dict:
        c = self.carry
        return {
            "execution": self.execution,
            "balance": self.balance,
            "admission": self.admission,
            "steal": self.policy.steal,
            "loads": [int(x) for x in self._loads],
            "queued": [int(x) for x in self.runtime.sizes()],
            "pending": self.pending,
            "served": len(self.done),
            "stolen": self.stolen,
            "migrated": self.migrated,
            "stalls": int(np.asarray(c["stalls"]).sum()),
            "kv_tokens": [
                paged_kv.pool_token_count(
                    _tmap(lambda x, i=i: x[i], c["pages"]),
                    np.asarray(c["owner"])[i], self.policy.page_size)
                for i in range(self.n_lanes)],
            "proportion": (self.controller.effective_proportion
                           if self.controller else
                           self.runtime.policy.proportion),
            "backend": self.runtime.ops.resolved,
            "telemetry": self.telemetry.summary(),
        }
