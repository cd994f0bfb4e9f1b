"""TEDAGuard — the paper's detector as a production training-loop feature.

Wraps any train step with streaming anomaly detection over training
telemetry (loss, global grad norm, per-group grad norms). An outlier
verdict (eq (6)) masks the optimizer update for that step (the gradients
are dropped, the model never sees the bad batch) — the loss-spike /
corrupt-batch / flipped-bit defense used in production LLM training, but
assumption-free and O(1)-state per monitored channel, exactly as TEDA
promises.

Fully jittable: the guard state lives inside the train state and the skip
is a `jnp.where` mask, so it composes with pjit/shard_map and costs a few
hundred scalar flops per step.  The monitored channels are packed
`repro.engine` state (one slot per telemetry channel) advanced with the
engine's single-sample fast path — the same per-stream contract the
serving gateway and the chunked StreamEngine use.

Also provides a host-side `StragglerDetector` (TEDA over per-step wall
times across hosts) for straggler detection.
"""
from __future__ import annotations

import time
from typing import TYPE_CHECKING, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.teda import TedaOutput

if TYPE_CHECKING:  # type-only: repro.core.__init__ <-> engine.state cycle
    from repro.engine.state import EngineState


def _engine():
    """Lazy import of the engine functional core.

    `repro.core.__init__` imports this module while `repro.engine.state`
    may itself be mid-import of `repro.core.teda` (either package can be
    entered first); deferring to call time breaks the cycle.
    """
    from repro.engine import state
    return state

__all__ = ["GuardConfig", "GuardState", "GuardVerdict", "guard_init",
           "guard_step", "apply_guard", "StragglerDetector"]


class GuardConfig(NamedTuple):
    m: float = 3.0           # eq (6) threshold multiplier
    warmup_steps: int = 20   # never skip before statistics stabilize
    exclude_outliers: bool = True  # don't absorb outliers into (mu, var)
    channels: int = 2        # monitored telemetry channels


class GuardState(NamedTuple):
    teda: "EngineState"      # packed per-channel engine state
    skipped: jnp.ndarray     # () int32 — total skipped steps
    last_outlier: jnp.ndarray  # (channels,) bool


class GuardVerdict(NamedTuple):
    skip: jnp.ndarray        # () bool — whether the update was masked
    per_channel: TedaOutput  # raw TEDA verdicts per channel


def guard_init(cfg: GuardConfig) -> GuardState:
    return GuardState(
        teda=_engine().engine_init(cfg.channels),
        skipped=jnp.zeros((), jnp.int32),
        last_outlier=jnp.zeros((cfg.channels,), bool),
    )


def guard_step(state: GuardState, metrics: jnp.ndarray, cfg: GuardConfig
               ) -> Tuple[GuardState, GuardVerdict]:
    """Score one step's telemetry vector metrics (channels,).

    Non-finite telemetry (NaN/inf loss or grad norm) is always an outlier.
    With `exclude_outliers`, flagged samples do not contaminate the TEDA
    statistics (the state update is rolled back), so a run of spikes stays
    detectable — this extends the paper (which always absorbs samples;
    tests/test_guard.py holds both behaviours).
    """
    eng = _engine()
    finite = jnp.isfinite(metrics)
    clean = jnp.where(finite, metrics, state.teda.mean)
    new_teda, out = eng.engine_step(state.teda, clean, cfg.m)

    in_warmup = state.teda.k[0] < cfg.warmup_steps
    outlier = jnp.logical_or(out.outlier, ~finite)
    trip = jnp.logical_and(jnp.any(outlier), ~in_warmup)

    if cfg.exclude_outliers:
        keep = jnp.logical_or(~outlier, in_warmup)
        new_teda = eng.EngineState(
            k=jnp.where(keep, new_teda.k, state.teda.k),
            mean=jnp.where(keep, new_teda.mean, state.teda.mean),
            var=jnp.where(keep, new_teda.var, state.teda.var),
            active=new_teda.active,
        )

    new_state = GuardState(
        teda=new_teda,
        skipped=state.skipped + trip.astype(jnp.int32),
        last_outlier=outlier,
    )
    return new_state, GuardVerdict(skip=trip, per_channel=out)


def apply_guard(skip: jnp.ndarray, new_tree, old_tree):
    """Mask a pytree update: where skip, keep old leaves (grad dropped)."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(skip, o, n), new_tree, old_tree)


class StragglerDetector:
    """Host-side TEDA over per-step wall-times (straggler mitigation).

    A training loop feeds it one duration per step (or per-host
    durations in multi-controller deployments); `check()` returns True
    when the latest step is eccentric per eq (6) — the signal used to
    trigger host replacement / checkpoint handoff at fleet scale.
    """

    def __init__(self, m: float = 3.0, warmup: int = 10):
        self.m = float(m)
        self.warmup = int(warmup)
        self.k = 0
        self.mean = 0.0
        self.var = 0.0
        self.trips = 0
        self._t0: Optional[float] = None

    def tick(self) -> None:
        self._t0 = time.perf_counter()

    def tock(self) -> bool:
        assert self._t0 is not None, "tick() before tock()"
        return self.check(time.perf_counter() - self._t0)

    def check(self, duration_s: float) -> bool:
        self.k += 1
        k = float(self.k)
        if self.k == 1:
            self.mean, self.var = duration_s, 0.0
            return False
        self.mean = (k - 1.0) / k * self.mean + duration_s / k
        d2 = (duration_s - self.mean) ** 2
        self.var = (k - 1.0) / k * self.var + d2 / k
        if self.var <= 0.0 or self.k <= self.warmup:
            return False
        ecc = 1.0 / k + d2 / (k * self.var)
        trip = ecc / 2.0 > (self.m ** 2 + 1.0) / (2.0 * k)
        self.trips += int(trip)
        return bool(trip)
