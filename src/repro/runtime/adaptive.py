"""Adaptive steal-proportion control for the executor.

The paper's ``steal(p)`` takes a static proportion; §V cites
Adnan-Sato-style dynamic chunk sizing as the natural extension.  Here the
master's observed queue sizes feed a small controller that servos the
proportion toward ``core.policy.adaptive_chunk``'s idle/busy-ratio
target:

* many idle workers + few victims -> steal a larger fraction so one
  round can feed several drained lanes from one victim;
* few idle workers -> steal less, preserving victim locality (the
  paper's argument for leaving the owner's hot head intact).

The feedback step itself is :func:`adaptive_update` — PURE jnp, float32
— so it runs in two places with one source of truth:

* **on device**, inside ``StealRuntime.run_fused``'s ``lax.scan`` carry,
  where the proportion is re-tuned every fused round without ever
  leaving the device (zero recompiles, zero host syncs);
* **on host**, via :class:`AdaptiveController`, for per-round driving
  (``StealRuntime.round``) and host-level consumers (the serving
  admission master) — the proportion is fed into the jitted superstep
  as a *traced* scalar, so updating it never recompiles.

Because both paths evaluate the identical float32 computation, a fused
k-round run follows the same proportion trajectory as k sequential
host-driven rounds.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import StealPolicy

__all__ = ["AdaptiveConfig", "AdaptiveController", "adaptive_update"]


@dataclasses.dataclass(frozen=True)
class AdaptiveConfig:
    """Controller bounds and dynamics.

    Attributes:
      min_proportion / max_proportion: clamp range (the paper's Fig. 7/8
        sweep stays within [0.1, 0.6]; stealing > 3/4 would invert the
        imbalance).
      gain: first-order smoothing toward the target (1.0 = jump straight
        to the target each round).  The BENCH_PR3 full-size sweep found
        gain/clamp indistinguishable on rounds-to-drain (every adaptive
        config drained the Fig. 9 DAG in the same 420 supersteps; wall
        differences were within noise), so only the sweep's unambiguous
        winner was promoted — static p=0.25, now the
        :class:`~repro.core.policy.StealPolicy` default — and the
        smoothing default stays 0.5, which also spreads work across
        more lanes on the DD branch-and-bound workload than an
        unsmoothed jump does.
    """

    min_proportion: float = 0.125
    max_proportion: float = 0.75
    gain: float = 0.5


def adaptive_update(proportion, sizes, *, policy: StealPolicy,
                    config: AdaptiveConfig) -> jnp.ndarray:
    """One feedback step: float32 scalar in, float32 scalar out.

    Pure jnp (usable inside jit / scan).  The target is
    ``core.policy.adaptive_chunk`` vectorized: scale the stolen
    proportion with the idle/busy imbalance, clamped to [0.125, 0.75],
    then first-order-smooth toward it.  When the plan can pair no
    (victim, thief) this round there is no transfer to size, so hold
    rather than servo on zero signal.
    """
    with jax.named_scope("adaptive"):
        sizes = jnp.asarray(sizes)
        p = jnp.asarray(proportion, jnp.float32)
        n_idle = jnp.sum((sizes <= policy.low_watermark).astype(jnp.int32))
        n_busy = jnp.sum((sizes >= policy.high_watermark).astype(jnp.int32))
        ratio = (n_idle.astype(jnp.float32)
                 / jnp.maximum(n_idle + n_busy, 1).astype(jnp.float32))
        target = jnp.clip(jnp.float32(policy.proportion) * 2.0 * ratio,
                          0.125, 0.75)
        p_new = p + jnp.float32(config.gain) * (target - p)
        p_new = jnp.clip(p_new, config.min_proportion,
                         config.max_proportion)
        return jnp.where((n_idle > 0) & (n_busy > 0), p_new, p)


class AdaptiveController:
    """Host-side wrapper: history + the NEXT round's proportion.

    Delegates the arithmetic to :func:`adaptive_update` so the host
    trajectory is bit-identical to the on-device fused one.
    """

    def __init__(self, policy: StealPolicy,
                 config: Optional[AdaptiveConfig] = None):
        self.policy = policy
        self.config = config or AdaptiveConfig()
        self.proportion = float(jnp.float32(policy.proportion))
        self.history: List[float] = [self.proportion]
        # Straggler response (train.fault.StragglerMonitor wiring): while
        # boosted, the proportion HANDED OUT is scaled up so the master
        # steals harder against a flagged-straggler lane for a bounded
        # number of rounds; the servo state itself is untouched, so the
        # boost decays to the normal trajectory instead of destabilizing
        # the feedback loop.
        self._boost_rounds_left = 0
        self._boost_factor = 1.0
        # Which lanes the active boost is attributed to, so reviving a
        # lane clears exactly its penalty (a lane-less flag attributes
        # to nobody and only ever decays by rounds).
        self._boost_lanes: set = set()

    def flag_straggler(self, rounds: int = 4, factor: float = 1.5,
                       lane: Optional[int] = None) -> None:
        """A straggler was flagged: boost the emitted steal proportion by
        ``factor`` (clamped to the config max) for the next ``rounds``
        controller updates.  ``lane`` attributes the boost so
        :meth:`clear_straggler` (revival) can cancel it."""
        self._boost_rounds_left = max(self._boost_rounds_left, int(rounds))
        self._boost_factor = float(factor)
        if lane is not None:
            self._boost_lanes.add(int(lane))

    def clear_straggler(self, lane: Optional[int] = None) -> None:
        """Cancel straggler penalty: for ``lane`` (a revived lane must
        not come back pre-penalized), or all of it when ``lane`` is
        None.  The boost only drops when no attributed lane remains —
        clearing one of two flagged lanes keeps the other's boost."""
        if lane is None:
            self._boost_lanes.clear()
            self._boost_rounds_left = 0
            self._boost_factor = 1.0
            return
        if int(lane) in self._boost_lanes:
            self._boost_lanes.discard(int(lane))
            if not self._boost_lanes:
                self._boost_rounds_left = 0
                self._boost_factor = 1.0

    @property
    def effective_proportion(self) -> float:
        """What the next round should actually use: the servo proportion,
        temporarily scaled while a straggler boost is active."""
        if self._boost_rounds_left > 0:
            return float(jnp.float32(min(
                self.proportion * self._boost_factor,
                self.config.max_proportion)))
        return self.proportion

    def update(self, sizes) -> float:
        """One feedback step from the post-round size vector."""
        p = float(adaptive_update(jnp.float32(self.proportion),
                                  jnp.asarray(np.asarray(sizes), jnp.int32),
                                  policy=self.policy, config=self.config))
        self.proportion = p
        self.history.append(p)
        if self._boost_rounds_left > 0:
            self._boost_rounds_left -= 1
            if self._boost_rounds_left == 0:
                self._boost_lanes.clear()
        return p

    def absorb(self, proportions_used, final_proportion) -> None:
        """Sync host state after an on-device fused run: ``proportions_used``
        are the k per-round values the scan consumed (element 0 is the
        pre-run proportion already in ``history``), ``final_proportion``
        the post-run carry value."""
        post = [float(x) for x in np.asarray(proportions_used)[1:]]
        self.proportion = float(final_proportion)
        self.history.extend(post + [self.proportion])
        if self._boost_rounds_left > 0:
            self._boost_rounds_left = max(
                0, self._boost_rounds_left - len(post) - 1)
            if self._boost_rounds_left == 0:
                self._boost_lanes.clear()
