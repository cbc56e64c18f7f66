"""Phase-attributed wall-clock for steal-runtime rounds.

The paper's claims are latency claims, but a dispatched round is one
opaque XLA program — there is no host-visible boundary between the
worker body, the exchange collective and the thief splice to put a
timer on.  Two mechanisms recover the split without touching the
committed computation:

**Unfused rounds (direct measurement).**  ``make_lane_step(stage=...)``
defines truncated *prefix* programs of the identical round: ``"worker"``
ends after the worker body, ``"exchange"`` ends after the block-exchange
collective (:func:`repro.core.master.exchange_probe`, whose returned
token data-depends on the spliced buffers so XLA cannot dead-code any of
the prefix).  The probe dispatches both prefixes on the SAME immutable
inputs the real round is about to consume (pure functions — results are
discarded, buffers are never donated), fences with
``jax.block_until_ready``, then lets the unchanged full round commit:

    worker_body = wall(P_worker)
    exchange    = wall(P_exchange) - wall(P_worker)
    splice      = wall(full round) - wall(P_exchange)

``adaptive_update`` is the host controller there, timed directly.

**Fused blocks (calibrated estimate).**  A ``lax.scan`` of k rounds
cannot be fenced per phase without breaking fusion (an in-trace
``jax.debug.callback`` costs ~0.4 ms per mark on CPU — an order of
magnitude over the <5 % overhead budget).  Instead the probe times the
whole dispatch, divides by the executed round count, and splits each
round by *calibrated phase fractions*: once per ``calibrate_every``
rounds it times the four prefix programs (worker / exchange / full /
full+adaptive) on the current state and caches the normalized deltas.
Fused samples are flagged ``estimated=True`` in the telemetry.

Compile-identity guarantee: prefix programs live in the runtime's
SEPARATE ``_probe_compiled`` cache — ``elastic.compile_count`` (which
audits ``_compiled``) is unchanged whether the probe is attached or
not, and with the probe disabled the dispatch path is byte-for-byte
today's code.  Because prefixes are pure and never donate, committed
results are bit-identical with the probe on or off.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

__all__ = ["PHASES", "PhaseSample", "PhaseProbe", "timed_call"]

# Phase order is load-bearing: calibration deltas and trace children
# are emitted in this order.
PHASES: Tuple[str, ...] = ("worker_body", "exchange", "splice",
                           "adaptive_update")


@dataclasses.dataclass(frozen=True)
class PhaseSample:
    """One round's wall-clock split, in seconds.

    ``estimated`` distinguishes the fused path (whole-dispatch wall
    split by calibrated fractions) from the unfused path (each phase
    bounded by real fences).  ``total`` is the wall actually attributed
    to the round — phases sum to it by construction.
    """

    worker_body: float
    exchange: float
    splice: float
    adaptive_update: float
    total: float
    estimated: bool = False

    def as_record(self) -> Dict[str, Any]:
        """The kwargs `Telemetry.record(phases=...)` consumes."""
        return {
            "t_worker": self.worker_body,
            "t_exchange": self.exchange,
            "t_splice": self.splice,
            "t_adaptive": self.adaptive_update,
            "t_round": self.total,
            "phase_estimated": self.estimated,
        }


def timed_call(fn, args) -> Tuple[float, Any]:
    """Wall seconds of one dispatch, fenced on its OUTPUTS.  The caller
    is responsible for input readiness (in the probe's use the inputs
    were just fenced or read back by the previous round)."""
    t0 = time.perf_counter()
    out = fn(*args)
    out = jax.block_until_ready(out)
    return time.perf_counter() - t0, out


class PhaseProbe:
    """Host-side probe state: the enable switch plus the per-worker-fn
    calibration cache for fused attribution.

    ``calibrate_every`` is the re-calibration cadence in ROUNDS (not
    dispatches): fused blocks re-time the prefix programs only when the
    cached fractions are at least this stale, so steady-state overhead
    is the amortized cost of four extra dispatches per
    ``calibrate_every`` rounds plus two clock reads per block.
    """

    def __init__(self, *, enabled: bool = True,
                 calibrate_every: int = 512) -> None:
        self.enabled = bool(enabled)
        self.calibrate_every = max(int(calibrate_every), 1)
        self.rounds_attributed = 0
        self.calibrations = 0
        self._fractions: Dict[Any, np.ndarray] = {}
        self._cal_round: Dict[Any, int] = {}

    # -- calibration cache ---------------------------------------------------

    def needs_calibration(self, key: Any, rounds_run: int) -> bool:
        if key not in self._fractions:
            return True
        return rounds_run - self._cal_round[key] >= self.calibrate_every

    def store_calibration(self, key: Any, parts, rounds_run: int) -> None:
        """Cache phase fractions from raw per-phase seconds (clamped to
        >= 0 and normalized; a degenerate all-zero measurement falls back
        to a uniform split rather than NaN)."""
        parts = np.maximum(np.asarray(parts, dtype=np.float64), 0.0)
        total = float(parts.sum())
        if total <= 0.0:
            parts = np.full((len(PHASES),), 1.0 / len(PHASES))
        else:
            parts = parts / total
        self._fractions[key] = parts
        self._cal_round[key] = int(rounds_run)
        self.calibrations += 1

    def fractions(self, key: Any) -> np.ndarray:
        return self._fractions[key]

    # -- sample construction -------------------------------------------------

    def direct_sample(self, *, t_worker: float, t_exchange: float,
                      t_full: float, t_adaptive: float) -> PhaseSample:
        """Unfused attribution by subtraction of fenced prefix walls.
        Negative differences (clock noise on a near-empty phase) clamp
        to zero; the residual re-lands in ``splice`` so phases still sum
        to the measured total."""
        worker = max(t_worker, 0.0)
        exchange = max(t_exchange - t_worker, 0.0)
        adaptive = max(t_adaptive, 0.0)
        splice = max(t_full - worker - exchange, 0.0)
        self.rounds_attributed += 1
        return PhaseSample(worker_body=worker, exchange=exchange,
                           splice=splice, adaptive_update=adaptive,
                           total=worker + exchange + splice + adaptive,
                           estimated=False)

    def estimated_sample(self, key: Any, per_round_s: float,
                         n: int = 1) -> PhaseSample:
        """Fused attribution: one round's share of the dispatch wall,
        split by the cached calibration fractions.  Every round of one
        fused block gets the same attribution, so callers compute the
        sample ONCE and reuse it for all ``n`` rounds (keeps the probed
        read-back loop's Python cost per block, not per round)."""
        f = self.fractions(key)
        parts = [float(per_round_s) * float(f[i]) for i in range(len(PHASES))]
        self.rounds_attributed += int(n)
        return PhaseSample(worker_body=parts[0], exchange=parts[1],
                           splice=parts[2], adaptive_update=parts[3],
                           total=float(per_round_s), estimated=True)
