"""Per-round observability for the steal runtime.

Host-side, numpy-only (it must also serve the serving controller, which
never touches a device): each rebalancing round appends one
:class:`RoundRecord` with the steal count, items/bytes moved, the
exchange payload (``bytes_moved`` — what the round's block collective
carried per lane, the Fig. 10 scaling metric), the queue-depth histogram
and imbalance statistics.  Wave-level consumers (the serving engine) append
:class:`WaveRecord` entries through the same object, and fault /
detector transitions land both as counters (:attr:`Telemetry.
fault_events`) and as a round-stamped event log (:attr:`Telemetry.
fault_log`) so one telemetry stream covers the master's rounds, the
workload's waves and the failures on a single logical-round timeline —
exactly what :mod:`repro.obs.trace` renders and
:mod:`repro.obs.metrics` exposes.

``summary()`` collapses the log into the benchmark-facing aggregates
(the DESIGN.md experiment sections consume these): a dict with

* ``rounds`` / ``steals`` / ``items_transferred`` /
  ``bytes_transferred`` / ``bytes_moved`` — lifetime round totals;
* ``proportion_mean`` / ``proportion_final`` / ``imbalance_final`` —
  adaptive-controller trajectory endpoints;
* ``waves`` / ``served`` / ``tokens`` (and ``migrated`` when nonzero) —
  only when wave records exist;
* ``requests`` + ``ttft_p50/p95/p99`` + ``latency_p50/p95/p99`` (in
  logical rounds) — only when request records exist;
* ``straggler_steps`` always, ``faults`` (the event counters dict) when
  any were recorded.

How a round's wall-clock splits is not recorded here: the host side
comes from :mod:`repro.obs.spans`, the device side from the named
scopes in the profiler's trace (DESIGN.md §11).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

__all__ = ["item_nbytes", "reduce_round_stats", "RoundRecord", "WaveRecord",
           "RequestRecord", "Telemetry"]


def item_nbytes(item_spec: Any) -> int:
    """Bytes per queue item — delegates to ``core.ops.item_nbytes``, the
    single source of truth (the master's ``bytes_moved`` uses the same
    accounting, so payload and transfer byte telemetry can't diverge)."""
    from repro.core.ops import item_nbytes as _impl

    return _impl(item_spec)


def reduce_round_stats(stats, *, n_workers: int, pod_size: Optional[int] = None
                       ) -> tuple:
    """Exact ``(n_steals, n_transferred, bytes_moved)`` for one round from
    per-lane ``RebalanceStats`` counters (numpy leaves, leading axis =
    lanes).

    This is the one reduction both executors share: the vmapped
    ``StealRuntime`` reads lanes of a stacked array, the mesh runtime
    reads the same layout after shard_map gathered each device's shard
    into lane order — so per-shard counters reduce to the identical
    exact ``RoundRecord`` regardless of where the lanes live.

    Flat mode: per-lane counters are replicated, so element 0 is exact.
    Hierarchical mode: lane ``(p, 0)`` carries pod p's intra-pod share;
    the cross-pod share lives in the ``*_xpod`` fields, nonzero only on
    lane-0 representatives and replicated across them — summing intra
    over pods and adding xpod ONCE is exact.  ``bytes_moved`` stays
    PER-LANE (the busiest lane's injection: its pod's intra-level
    payload plus the pod-level share)."""
    if pod_size is None:
        return (int(np.asarray(stats.n_steals).reshape(-1)[0]),
                int(np.asarray(stats.n_transferred).reshape(-1)[0]),
                int(np.asarray(stats.bytes_moved).reshape(-1)[0]))
    n_pods = n_workers // pod_size
    rep = lambda x: np.asarray(x).reshape(n_pods, -1)[:, 0]
    n_steals = int(rep(stats.n_steals).sum()) + int(
        rep(stats.n_steals_xpod)[0])
    n_transferred = int(rep(stats.n_transferred).sum()) + int(
        rep(stats.n_transferred_xpod)[0])
    bytes_moved = int(rep(stats.bytes_moved).max()) + int(
        rep(stats.bytes_moved_xpod)[0])
    return n_steals, n_transferred, bytes_moved


@dataclasses.dataclass(frozen=True)
class RoundRecord:
    """One rebalancing round, as observed by the master."""

    round: int
    proportion: float          # steal proportion used THIS round
    n_steals: int              # victim->thief transfers planned
    n_transferred: int         # items moved
    transfer_bytes: int        # payload bytes moved
    bytes_moved: int           # exchange payload, busiest lane's view
    sizes_total: int
    sizes_max: int
    sizes_mean: float
    depth_hist: Sequence[int]  # queue-depth histogram over workers

    @property
    def imbalance(self) -> float:
        """max/mean load ratio (1.0 = perfectly balanced)."""
        return self.sizes_max / self.sizes_mean if self.sizes_mean else 0.0


@dataclasses.dataclass(frozen=True)
class WaveRecord:
    """One workload wave (e.g. a serving engine tick), as observed by
    whoever drives the rounds — same stream, coarser granularity.

    The SLO fields are percentiles over every :class:`RequestRecord`
    completed up to and including this wave (in logical rounds — the
    deterministic clock all execution modes share), filled in by
    :meth:`Telemetry.record_wave` whenever request records exist."""

    wave: int
    served: int                # requests completed this wave
    tokens: int                # tokens generated this wave (0 if n/a)
    loads: Sequence[int]       # per-worker load after the wave
    evicted: int = 0           # workers evicted (cumulative) at this wave
    stragglers: int = 0       # straggler flags raised this wave
    migrated: int = 0          # in-flight requests migrated (KV and all)
    ttft_p50: float = 0.0      # admit -> first-token percentiles (rounds)
    ttft_p95: float = 0.0
    ttft_p99: float = 0.0
    latency_p50: float = 0.0   # admit -> finish percentiles (rounds)
    latency_p95: float = 0.0
    latency_p99: float = 0.0
    round: int = -1            # logical round the wave closed at (-1 =
    #                            recorded before round alignment existed)


@dataclasses.dataclass(frozen=True)
class RequestRecord:
    """One served request's admit -> first-token -> finish timeline,
    stamped in LOGICAL rounds (the per-lane round counter every
    execution mode advances identically) so SLO telemetry is
    deterministic and bit-comparable across host/vmap/mesh."""

    rid: int
    admit: int                 # round the request was admitted
    first: int                 # round the first token was generated
    finish: int                # round the last token was generated
    tokens: int                # tokens actually generated

    @property
    def ttft(self) -> int:
        """Time-to-first-token, in rounds."""
        return self.first - self.admit

    @property
    def latency(self) -> int:
        """Admit-to-finish latency, in rounds."""
        return self.finish - self.admit


def _percentiles(values) -> tuple:
    """(p50, p95, p99) of a non-empty value sequence."""
    arr = np.asarray(values, np.float64)
    return tuple(float(np.percentile(arr, p)) for p in (50.0, 95.0, 99.0))


class Telemetry:
    """Append-only per-round log + aggregate summary."""

    def __init__(self, item_bytes: int = 1, capacity: Optional[int] = None,
                 n_bins: int = 8):
        self.item_bytes = int(item_bytes)
        self.capacity = capacity
        self.n_bins = n_bins
        self.rounds: List[RoundRecord] = []
        self.waves: List[WaveRecord] = []
        self.requests: List[RequestRecord] = []
        # Resilience counters: kills / restarts / evictions / shrink /
        # grow events and straggler flags, recorded by the runtime's
        # fault layer next to the round + wave streams so one telemetry
        # object tells the whole story of a faulted run.
        self.fault_events: Dict[str, int] = {}
        # Round-stamped event log: (kind, lane, round) per record_fault
        # call (lane -1 = not lane-attributed) — what the trace exporter
        # renders as instant events on the round timeline.
        self.fault_log: List[tuple] = []
        self.straggler_steps = 0

    def record(self, *, sizes, n_steals: int, n_transferred: int,
               proportion: float, bytes_moved: int = 0) -> RoundRecord:
        """Append one round."""
        sizes = np.asarray(sizes)
        hi = self.capacity if self.capacity else max(int(sizes.max()), 1)
        hist, _ = np.histogram(sizes, bins=self.n_bins, range=(0, hi))
        rec = RoundRecord(
            round=len(self.rounds),
            proportion=float(proportion),
            n_steals=int(n_steals),
            n_transferred=int(n_transferred),
            transfer_bytes=int(n_transferred) * self.item_bytes,
            bytes_moved=int(bytes_moved),
            sizes_total=int(sizes.sum()),
            sizes_max=int(sizes.max()) if sizes.size else 0,
            sizes_mean=float(sizes.mean()) if sizes.size else 0.0,
            depth_hist=tuple(int(x) for x in hist),
        )
        self.rounds.append(rec)
        return rec

    def record_wave(self, *, loads, served: int, tokens: int = 0,
                    evicted: int = 0, stragglers: int = 0,
                    migrated: int = 0) -> WaveRecord:
        """Append one workload wave (serving tick, solver epoch, ...).
        When request records exist (:meth:`record_request`), the wave
        carries the cumulative SLO percentiles at this point in time."""
        slo = {}
        if self.requests:
            t50, t95, t99 = _percentiles([r.ttft for r in self.requests])
            l50, l95, l99 = _percentiles([r.latency for r in self.requests])
            slo = dict(ttft_p50=t50, ttft_p95=t95, ttft_p99=t99,
                       latency_p50=l50, latency_p95=l95, latency_p99=l99)
        rec = WaveRecord(
            wave=len(self.waves),
            served=int(served),
            tokens=int(tokens),
            loads=tuple(int(x) for x in np.asarray(loads).reshape(-1)),
            evicted=int(evicted),
            stragglers=int(stragglers),
            migrated=int(migrated),
            round=len(self.rounds),
            **slo,
        )
        self.waves.append(rec)
        return rec

    def record_request(self, *, rid: int, admit: int, first: int,
                       finish: int, tokens: int) -> RequestRecord:
        """Append one served request's admit/first-token/finish stamps
        (logical rounds)."""
        rec = RequestRecord(rid=int(rid), admit=int(admit), first=int(first),
                            finish=int(finish), tokens=int(tokens))
        self.requests.append(rec)
        return rec

    def record_fault(self, kind: str, n: int = 1,
                     lane: Optional[int] = None) -> None:
        """Count one resilience event (``"kill"`` / ``"restart"`` /
        ``"evict"`` / ``"suspect"`` / ``"shrink"`` / ``"grow"`` /
        ``"straggler"`` / ...).  ``lane`` attributes the event to a
        queue lane in the round-stamped :attr:`fault_log` (one log entry
        per call, stamped with the current round count).  Straggler
        flags additionally feed :attr:`straggler_steps`, the counter
        :meth:`summary` exports."""
        self.fault_events[kind] = self.fault_events.get(kind, 0) + int(n)
        self.fault_log.append((kind, -1 if lane is None else int(lane),
                               len(self.rounds)))
        if kind == "straggler":
            self.straggler_steps += int(n)

    # -- aggregates ----------------------------------------------------------

    @property
    def total_steals(self) -> int:
        return sum(r.n_steals for r in self.rounds)

    @property
    def total_transferred(self) -> int:
        return sum(r.n_transferred for r in self.rounds)

    @property
    def total_transfer_bytes(self) -> int:
        return sum(r.transfer_bytes for r in self.rounds)

    @property
    def total_bytes_moved(self) -> int:
        """Total per-lane exchange payload across rounds (the number the
        compact superstep shrinks by ~W vs the dense one)."""
        return sum(r.bytes_moved for r in self.rounds)

    @property
    def total_served(self) -> int:
        return sum(w.served for w in self.waves)

    @property
    def total_tokens(self) -> int:
        return sum(w.tokens for w in self.waves)

    def summary(self) -> Dict[str, Any]:
        props = [r.proportion for r in self.rounds]
        out = {
            "rounds": len(self.rounds),
            "steals": self.total_steals,
            "items_transferred": self.total_transferred,
            "bytes_transferred": self.total_transfer_bytes,
            "bytes_moved": self.total_bytes_moved,
            "proportion_mean": float(np.mean(props)) if props else 0.0,
            "proportion_final": props[-1] if props else 0.0,
            "imbalance_final": self.rounds[-1].imbalance if self.rounds
            else 0.0,
        }
        if self.waves:
            out["waves"] = len(self.waves)
            out["served"] = self.total_served
            out["tokens"] = self.total_tokens
            migrated = sum(w.migrated for w in self.waves)
            if migrated:
                out["migrated"] = migrated
        if self.requests:
            t50, t95, t99 = _percentiles([r.ttft for r in self.requests])
            l50, l95, l99 = _percentiles([r.latency for r in self.requests])
            out["requests"] = len(self.requests)
            out["ttft_p50"], out["ttft_p95"], out["ttft_p99"] = t50, t95, t99
            out["latency_p50"] = l50
            out["latency_p95"] = l95
            out["latency_p99"] = l99
        out["straggler_steps"] = self.straggler_steps
        if self.fault_events:
            out["faults"] = dict(self.fault_events)
        return out
