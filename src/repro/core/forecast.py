"""FCFP forecasting: harmonic regression + EWMA residual tracking, in JAX.

The paper's FCFP term is "forecasted carbon footprint based on historical
data".  We implement the standard grid-CI forecaster: a Fourier basis over
daily / weekly / annual periods fit by least squares (jnp.linalg.lstsq),
plus an EWMA of recent residuals to absorb weather fronts.  ``vmap`` over
regions gives the fleet forecaster.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

PERIODS = (24.0, 168.0, 8760.0)
HARMONICS = (3, 2, 1)


def _active_periods(T: int) -> Tuple[Tuple[float, int], ...]:
    """Periods with at least one full cycle of support in a T-hour window.

    A harmonic much longer than the window (e.g. the 8760 h annual term fit
    on a few days) is near-collinear with the intercept; float32 lstsq then
    amplifies the ~1e-7 curvature difference into multi-thousand-unit
    coefficient pairs that cancel in-sample and explode out-of-sample."""
    return tuple((p, nh) for p, nh in zip(PERIODS, HARMONICS) if T >= p)


def _design(t: jax.Array,
            periods: Tuple[Tuple[float, int], ...]) -> jax.Array:
    """Fourier design matrix (T, F) over the given (period, harmonics)."""
    cols = [jnp.ones_like(t)]
    for period, nh in periods:
        for k in range(1, nh + 1):
            w = 2 * jnp.pi * k * t / period
            cols.append(jnp.cos(w))
            cols.append(jnp.sin(w))
    return jnp.stack(cols, axis=-1)


@functools.partial(jax.jit, static_argnames=("horizon",))
@jax.named_scope("forecast")
def fit_forecast(history: jax.Array, horizon: int,
                 t0: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Fit on ``history`` (T,) starting at absolute hour t0; forecast the
    next ``horizon`` hours.  Returns (forecast (horizon,), coef).

    ``coef`` is always padded to the full-basis width so the output shape
    is independent of how many periods the window supports (vmap-safe)."""
    T = history.shape[0]
    periods = _active_periods(T)
    n_full = 1 + 2 * sum(HARMONICS)
    t_hist = t0 + jnp.arange(T, dtype=jnp.float32)
    X = _design(t_hist, periods)
    coef, *_ = jnp.linalg.lstsq(X, history.astype(jnp.float32))
    resid = history - X @ coef
    # Weather-regime correction: the last day's residual *pattern* persists
    # (wind fronts last ~days), decaying toward the climatological fit.
    # Histories shorter than a day only have L < 24 residuals: cycle
    # through those L explicitly — relying on jnp's out-of-bounds gather
    # clamp would silently repeat the last residual 24-L times per day.
    h = jnp.arange(horizon, dtype=jnp.float32)
    L = min(T, 24)
    last_day = resid[-L:]
    pattern = last_day[jnp.mod(h.astype(jnp.int32), L)]
    decay = 0.82 ** (h / 24.0 + 0.25)
    t_fut = t0 + T + h
    fc = _design(t_fut, periods) @ coef + pattern * decay
    coef = jnp.pad(coef, (0, n_full - coef.shape[0]))
    return jnp.maximum(fc, 0.0), coef


forecast_regions = jax.vmap(fit_forecast, in_axes=(0, None, None),
                            out_axes=(0, 0))


@functools.partial(jax.jit, static_argnames=("horizon",))
def persistence_forecast(history: jax.Array, horizon: int) -> jax.Array:
    """Persistence-of-day fallback: cycle the last ``min(T, 24)`` observed
    hours across the horizon.  This is the graceful-degradation forecast
    the simulator substitutes when the forecast service is out (see
    ``faults.FaultConfig.fc_outage``/``fc_dropout``) — it needs only the
    same observed window ``fit_forecast`` reads, no fitted coefficients,
    and it is exactly the skill baseline ``forecast_skill`` scores
    against."""
    L = min(history.shape[0], 24)
    return jnp.tile(history[-L:], (horizon + L - 1) // L)[:horizon]


persistence_regions = jax.vmap(persistence_forecast, in_axes=(0, None),
                               out_axes=0)


def green_window_signals(fc: jax.Array, region_pue: jax.Array,
                         lookahead_h: int, discount: float = 0.9
                         ) -> Tuple[jax.Array, jax.Array]:
    """Green-window extraction over a region forecast tensor.

    ``fc`` is ``(..., R, H)`` forecast CI (any leading batch axes — the
    scanned simulator passes the whole ``(T, R, H)`` trajectory tensor,
    and the batched ensemble vmaps an ``(E, T, R, H)`` grid over it, so
    the reduction must stay shape-polymorphic in the leading axes);
    ``region_pue`` is the per-region representative PUE (``+inf`` rows for
    regions with no nodes, so they can never win a min).  Returns

    - ``la_ci`` ``(..., R)``: discount-weighted mean forecast CI over the
      next ``L = min(lookahead_h, H)`` hours (weights ``discount**h``,
      normalized) — the planner's "what does staying in this region cost"
      signal, robust to ``horizon < lookahead_h`` by clamping;
    - ``gw_min`` ``(...,)``: the greenest achievable CFP *rate*
      (CI x PUE) at any single hour inside the window — the green-window
      gate reference (migrate only when the present is within
      ``green_gate`` x of this).
    """
    L = max(1, min(int(lookahead_h), fc.shape[-1]))
    w = jnp.asarray(discount, jnp.float32) ** jnp.arange(L,
                                                         dtype=jnp.float32)
    w = w / jnp.sum(w)
    la_ci = jnp.sum(fc[..., :L] * w, axis=-1)
    # node-less regions are masked explicitly rather than relying on the
    # fc * inf product: fit_forecast clamps forecasts at exactly 0.0, and
    # 0 * inf = NaN would silently poison the min
    gw_min = jnp.min(jnp.where(jnp.isfinite(region_pue)[..., :, None],
                               fc[..., :L] * region_pue[..., :, None],
                               jnp.inf), axis=(-2, -1))
    return la_ci, gw_min


def forecast_skill(history: jax.Array, test: jax.Array) -> jax.Array:
    """MAE ratio vs 24h-persistence baseline (<1 means we beat persistence)."""
    fc, _ = fit_forecast(history, test.shape[0])
    mae = jnp.mean(jnp.abs(fc - test))
    persist = persistence_forecast(history, test.shape[0])
    mae_p = jnp.mean(jnp.abs(persist - test))
    return mae / jnp.maximum(mae_p, 1e-9)
