"""graft-watch: in-graph cross-rank health aggregation.

The telemetry ring (:mod:`grace_tpu.telemetry.state`) records *per-rank*
scalars and the host aggregates them at flush time — which is exactly the
wrong shape for the question that matters at scale: **is one rank drifting
away from the fleet?** ScaleCom (PAPERS.md) shows top-k sparsification
degrading with world size, and the earliest observable symptom is a single
rank's compression error creeping above its peers — a signal the PR-1 guard
cannot see (the values are finite) and the PR-3 consensus audit cannot see
either (residuals and compression error are *legitimately* per-rank, so
they are deliberately outside the fingerprint).

This module computes the cross-rank view **in-graph**, on a window
boundary, for the cost of one tiny collective:

* every rank stacks its local health scalars — pre-exchange gradient norm,
  relative compression error, error-feedback residual norm — into one
  (3,)-float vector;
* ``lax.all_gather`` moves the vectors over the mesh axis (``(W-1)·12``
  bytes received per rank — 84 B at W=8);
* from the gathered ``(W, 3)`` matrix every rank derives the replicated
  cross-rank **mean/min/max** per metric, its own **skew** (deviation from
  the replicated mean), and the replicated ``skew_max``/``skew_rank`` pair
  (the worst relative compression-error deviation and the rank holding it
  — the input channel an in-graph adaptive controller can act on without a
  host round-trip);
* the row lands in a bounded per-rank ring (:class:`WatchState`, sharded
  exactly like the telemetry ring) keyed by the GraceState step counter,
  so the host reader reconstructs the full per-rank skew *vector* from the
  world axis of one flush transfer.

Why a collective and not a host join: the per-rank telemetry rings already
reach the host, so the mean/min/max *could* be joined there — but only
after a flush (a window too late to gate anything in-graph), only on the
host (the closed-loop controller of ROADMAP item 5 needs the skew *inside*
the jitted step), and only by trusting host-side code to reproduce the
replicated reduction every rank would have agreed on. The all_gather makes
the summary a *replicated in-graph fact* — every rank provably holds the
same mean and the same offender election, the same property the consensus
audit builds on — and its wire cost is folded into the telemetry ring's
``wire_bytes``/``wire_bytes_ici``/``wire_bytes_dcn`` the same honest way
``audit_bytes`` is (see IMPLEMENTING.md, "Why skew is a collective").

Gating mirrors the consensus audit: a ``lax.cond`` on
``count % window == 0`` whose predicate derives from the replicated step
counter, so graft-lint's collective-consistency pass blesses the
branch-divergent gather (see the ``*-watch*`` entries in
``analysis/configs.py``) and non-boundary steps pay ~nothing.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["WATCH_FIELDS", "WATCH_FIELD_INDEX", "WATCH_METRICS",
           "WatchConfig", "WatchState", "normalize_watch", "watch_init",
           "watch_gather_bytes", "watch_record"]

# The local health scalars gathered cross-rank, in gather-column order.
WATCH_METRICS = ("grad_norm", "compression_error", "residual_norm")

# Ring columns of one watch row. The host-side reducer mirrors the
# telemetry FIELDS convention: "first" marks values replicated across ranks
# (derived from the gathered matrix, identical everywhere); "gather" marks
# genuinely per-rank values the reader re-assembles into a W-vector from
# the ring's sharded world axis — the host-side twin of the in-graph
# all_gather.
WATCH_FIELDS = (
    ("grad_norm_mean", "first"),
    ("grad_norm_min", "first"),
    ("grad_norm_max", "first"),
    ("compression_error_mean", "first"),
    ("compression_error_min", "first"),
    ("compression_error_max", "first"),
    ("residual_norm_mean", "first"),
    ("residual_norm_min", "first"),
    ("residual_norm_max", "first"),
    ("grad_norm_skew", "gather"),          # own value − replicated mean
    ("compression_error_skew", "gather"),
    ("residual_norm_skew", "gather"),
    ("skew_max", "first"),    # max relative compression-error deviation
    ("skew_rank", "first"),   # mesh index holding skew_max (the offender
                              # election — replicated, controller-ready)
    ("watch_bytes", "first"),  # the gather's received bytes this row
)

WATCH_FIELD_INDEX = {name: i for i, (name, _) in enumerate(WATCH_FIELDS)}


@dataclasses.dataclass(frozen=True)
class WatchConfig:
    """Static graft-watch knobs (hashable — safe inside jit closures).

    ``window`` — steps between cross-rank summaries (the ``lax.cond`` gate
    on ``GraceState.count``, the consensus ``audit_every`` idiom).
    ``capacity`` bounds the on-device summary ring; size it to at least
    ``flush_interval / window`` rows or the reader sees wraparound (counted,
    never silent, like the telemetry ring).
    """

    window: int = 10
    capacity: int = 16

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"watch window must be >= 1; got {self.window}")
        if self.capacity < 1:
            raise ValueError(f"watch capacity must be >= 1; "
                             f"got {self.capacity}")


def normalize_watch(watch):
    """Accept the ergonomic spellings of the watch knob, mirroring
    telemetry/consensus: None/False (off), True (defaults), int (window),
    dict (config kwargs), or a WatchConfig."""
    if watch is None or watch is False:
        return None
    if watch is True:
        return WatchConfig()
    if isinstance(watch, WatchConfig):
        return watch
    if isinstance(watch, int):
        return WatchConfig(window=watch)
    if isinstance(watch, dict):
        return WatchConfig(**watch)
    raise TypeError(f"watch must be None/bool/int/dict/WatchConfig; "
                    f"got {type(watch).__name__}")


class WatchState(NamedTuple):
    """Bounded on-device ring of cross-rank health summaries.

    Per-rank data like the telemetry ring (the skew columns genuinely
    differ per rank; the replicated columns are simply stored by everyone),
    so in the global view each leaf carries a leading world axis sharded
    over the mesh — ``partition_specs`` handles it alongside ``telem``.
    Rows are keyed by the GraceState step counter; ``-1`` = never written.
    """

    rings: jax.Array   # (capacity, len(WATCH_FIELDS)) float32 summary rows
    steps: jax.Array   # (capacity,) int32 step id per row; -1 = unwritten


def watch_init(config: WatchConfig) -> WatchState:
    return WatchState(
        rings=jnp.zeros((config.capacity, len(WATCH_FIELDS)), jnp.float32),
        steps=jnp.full((config.capacity,), -1, jnp.int32))


def _axis_size(axis_name: str) -> int:
    """Static size of the bound mesh axis. Not ``grace_tpu.core.axis_size``:
    this package must not import ``core`` (which imports :mod:`scopes`;
    see the package docstring)."""
    return lax.axis_size(axis_name)


def watch_gather_bytes(world: int) -> int:
    """Received bytes per rank of one watch gather: every other rank's
    (len(WATCH_METRICS),) float32 health vector. The number folded into the
    telemetry row's wire_bytes on window-boundary steps — and the number
    graft-lint's wire pass counts from the traced all_gather."""
    return max(0, world - 1) * len(WATCH_METRICS) * 4


def watch_record(watch: WatchState, count: jax.Array, values,
                 axis_name: str, due: jax.Array) -> WatchState:
    """Maybe-write one cross-rank summary row at slot ``count % capacity``.

    ``values`` maps each :data:`WATCH_METRICS` name to this rank's local
    scalar; ``due`` is the replicated window-boundary predicate (computed
    by the caller so the wire-byte fold can share it). The all_gather —
    the one collective graft-watch costs — runs only in the taken branch;
    the predicate descends from the replicated step counter, which is what
    lets every rank take the same branch (and graft-lint prove it).
    """
    missing = [m for m in WATCH_METRICS if m not in values]
    if missing:
        raise KeyError(f"watch_record missing metrics {missing}")
    local = jnp.stack([jnp.asarray(values[m], jnp.float32).reshape(())
                       for m in WATCH_METRICS])
    world = int(_axis_size(axis_name))

    def write(w: WatchState) -> WatchState:
        gathered = lax.all_gather(local, axis_name, axis=0,
                                  tiled=False)              # (W, 3)
        mean = jnp.mean(gathered, axis=0)
        mn = jnp.min(gathered, axis=0)
        mx = jnp.max(gathered, axis=0)
        skew = local - mean                                  # own deviation
        err_col = WATCH_METRICS.index("compression_error")
        rel = jnp.abs(gathered[:, err_col] - mean[err_col]) \
            / jnp.maximum(jnp.abs(mean[err_col]),
                          jnp.asarray(1e-12, jnp.float32))
        row = jnp.concatenate([
            jnp.stack([mean[0], mn[0], mx[0],
                       mean[1], mn[1], mx[1],
                       mean[2], mn[2], mx[2]]),
            skew,
            jnp.stack([jnp.max(rel),
                       jnp.argmax(rel).astype(jnp.float32),
                       jnp.asarray(float(watch_gather_bytes(world)),
                                   jnp.float32)]),
        ])
        idx = jnp.mod(count, w.steps.shape[0]).astype(jnp.int32)
        return WatchState(rings=w.rings.at[idx].set(row),
                          steps=w.steps.at[idx].set(
                              jnp.asarray(count, jnp.int32)))

    return lax.cond(jnp.asarray(due, jnp.bool_), write, lambda w: w, watch)
