"""The algorithm lanes, written once over abstract reductions.

All device layouts share this math; they differ only in how per-resource
totals are computed from per-lease values and broadcast back:

  * edge list   ([E] values,  sorted segment ids): segsum = segment_sum,
    expand = totals[rid] — kernels.solve_edges (CPU/general, and the
    sharded path, where segsum additionally psums across the mesh)
  * dense bucket ([R, K] values): segsum = sum(axis=1), expand =
    totals[:, None] — dense.solve_dense (the TPU-optimal layout)

Semantics are the per-tick snapshot semantics defined by the numpy oracles
in doorman_tpu.algorithms.tick; every layout must match them bit-for-bit
on representable inputs.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from doorman_tpu.algorithms.kinds import AlgoKind
from doorman_tpu.algorithms.tick import BALANCED_ROUNDS, FILL_ITERS

_REFINE_ITERS = 2

# The lanes whose fill is an iterative per-row computation in the row
# layout: row-layout callers may restrict each to its own rows via the
# compact gather→solve→scatter (lane_rows / fair_rows below).
ITERATIVE_KINDS = frozenset({
    int(AlgoKind.FAIR_SHARE),
    int(AlgoKind.MAX_MIN_FAIR),
    int(AlgoKind.BALANCED_FAIRNESS),
    int(AlgoKind.PROPORTIONAL_FAIRNESS),
})


def _bisect_iters(dtype) -> int:
    """Bisection only needs to separate the saturation ratios (the final
    closed-form snap recovers exact arithmetic); 2^-30 relative suffices
    for f32, 2^-48 for f64."""
    return 48 if jnp.dtype(dtype).itemsize >= 8 else 30

# lease-shaped values -> per-resource totals, and back.
Reduce = Callable[[jax.Array], jax.Array]
Expand = Callable[[jax.Array], jax.Array]


def waterfill_level(
    wants: jax.Array,  # lease-shaped, already masked (inactive -> 0)
    weights: jax.Array,  # lease-shaped, masked
    active: jax.Array,  # lease-shaped bool
    capacity: jax.Array,  # per-resource
    segsum: Reduce,
    segmax: Reduce,
    expand: Expand,
) -> jax.Array:
    """Per-resource water level for weighted max-min fair share: bisection
    to locate the saturated set, then a closed-form snap
    L = (capacity - sum_sat_wants) / sum_unsat_weights that reproduces the
    sorting-based numpy oracle's arithmetic exactly. For underloaded
    resources the level is the max saturation ratio (everyone satisfied)."""
    dtype = wants.dtype
    zero = jnp.zeros((), dtype)
    sum_wants = segsum(wants)
    safe_w = jnp.maximum(weights, jnp.finfo(dtype).tiny)
    ratio = jnp.where(weights > 0, wants / safe_w, zero)
    max_ratio = segmax(jnp.where(active, ratio, jnp.full((), -jnp.inf, dtype)))
    max_ratio = jnp.where(jnp.isfinite(max_ratio), max_ratio, 0.0)
    underloaded = sum_wants <= capacity

    def granted_at(level):
        return segsum(jnp.minimum(wants, expand(level) * weights))

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi) * 0.5
        need_more = granted_at(mid) < capacity
        return jnp.where(need_more, mid, lo), jnp.where(need_more, hi, mid)

    lo = jnp.zeros_like(capacity)
    hi = jnp.maximum(max_ratio, 0.0)
    lo, hi = jax.lax.fori_loop(0, _bisect_iters(dtype), body, (lo, hi))
    level = hi
    for _ in range(_REFINE_ITERS):
        sat = wants <= expand(level) * weights
        sat_wants = segsum(jnp.where(sat, wants, zero))
        unsat_weight = segsum(jnp.where(sat, zero, weights))
        exact = jnp.where(
            unsat_weight > 0,
            (capacity - sat_wants)
            / jnp.maximum(unsat_weight, jnp.finfo(dtype).tiny),
            level,
        )
        level = jnp.where(underloaded, level, jnp.maximum(exact, 0.0))
    return jnp.where(underloaded, max_ratio, level)


def waterfill_level_compact(
    wants: jax.Array,  # [R, K], row layout (one row = one resource)
    weights: jax.Array,  # [R, K]
    active: jax.Array,  # [R, K] bool
    capacity: jax.Array,  # [R]
    fair_rows: jax.Array,  # [F] int32, every FAIR_SHARE row (repeats ok)
) -> jax.Array:
    """Row-layout water level with the bisection restricted to the rows
    that actually run FAIR_SHARE. The per-row arithmetic of
    `waterfill_level` is independent across rows in the row layout
    (segsum/segmax reduce within a row), so gathering the fair rows,
    bisecting on the [F, K] subtable, and scattering the levels back is
    BIT-IDENTICAL to the full-table bisection for those rows — while the
    other rows (whose level the where-chain never selects) skip the
    ~50-pass bisection entirely. `fair_rows` may repeat indices
    (padding to a bucketed static shape): duplicate scatters write the
    same value. Non-fair rows read level 0, which no lane consumes."""
    wf = jnp.take(wants, fair_rows, axis=0)
    sf = jnp.take(weights, fair_rows, axis=0)
    af = jnp.take(active, fair_rows, axis=0)
    cf = jnp.take(capacity, fair_rows, axis=0)
    lvl = waterfill_level(
        wf, sf, af, cf,
        segsum=lambda v: v.sum(axis=1),
        segmax=lambda v: v.max(axis=1),
        expand=lambda totals: totals[:, None],
    )
    return jnp.zeros_like(capacity).at[fair_rows].set(lvl, mode="drop")


def iterfill_level(
    wants: jax.Array,  # lease-shaped, masked (inactive -> 0)
    weights: jax.Array,  # lease-shaped, masked
    capacity: jax.Array,  # per-resource
    segsum: Reduce,
    expand: Expand,
) -> jax.Array:
    """Per-resource water level by the fast-converging direct fill
    iteration (arxiv 2310.09699; oracle arithmetic in
    algorithms.tick.waterfill_level_iterative — expression-for-
    expression the same update, which is what pins the MAX_MIN_FAIR /
    PROPORTIONAL_FAIRNESS lanes to their host references): start at
    the even split, freeze the saturated set, re-level the remainder.
    The level is monotone non-decreasing, so `maximum` doubles as the
    convergence mask; FILL_ITERS bounds the unroll (one bottleneck
    cascade freezes per step at worst; deeper cascades keep the last —
    still feasible — level). Only meaningful for overloaded rows; the
    caller's fits-where never selects the underloaded ones."""
    dtype = wants.dtype
    zero = jnp.zeros((), dtype)
    tiny = jnp.finfo(dtype).tiny
    level = capacity / jnp.maximum(segsum(weights), tiny)

    def body(_, level):
        sat = wants <= expand(level) * weights
        sat_wants = segsum(jnp.where(sat, wants, zero))
        unsat_w = segsum(jnp.where(sat, zero, weights))
        level_new = (capacity - sat_wants) / jnp.maximum(unsat_w, tiny)
        return jnp.where(
            unsat_w > 0, jnp.maximum(level, level_new), level
        )

    return jax.lax.fori_loop(0, FILL_ITERS, body, level)


def iterfill_level_compact(
    wants: jax.Array,  # [R, K], row layout
    weights: jax.Array,  # [R, K]
    capacity: jax.Array,  # [R]
    rows: jax.Array,  # [F] int32 rows running this lane (repeats ok)
) -> jax.Array:
    """Row-layout iterative fill restricted to this lane's rows — the
    same gather→solve→scatter as waterfill_level_compact, bit-identical
    per row by per-row independence. Non-selected rows read level 0,
    which no lane consumes."""
    lvl = iterfill_level(
        jnp.take(wants, rows, axis=0),
        jnp.take(weights, rows, axis=0),
        jnp.take(capacity, rows, axis=0),
        segsum=lambda v: v.sum(axis=1),
        expand=lambda totals: totals[:, None],
    )
    return jnp.zeros_like(capacity).at[rows].set(lvl, mode="drop")


def balanced_fill(
    wants: jax.Array,  # lease-shaped, masked
    weights: jax.Array,  # lease-shaped, masked (subclients)
    active: jax.Array,  # lease-shaped bool
    capacity: jax.Array,  # per-resource
    segsum: Reduce,
    segmax: Reduce,
    expand: Expand,
) -> jax.Array:
    """Balanced-fairness grants by the recursive cap-peeling formula
    (arxiv 1711.02880 single-pool instantiation; oracle arithmetic in
    algorithms.tick.balanced_theta): shares proportional to weights,
    scaled by the most binding constraint ratio θ; each round the
    classes at the max cap ratio freeze at their wants and leave the
    recursion — the peel condition compares ratios to their own segmax,
    so the argmax class peels by exact float equality (guaranteed
    progress, no epsilon). A class that wants nothing (ratio x/0 =
    inf) starts fixed at 0, so it never dilutes the pool; a subnormal
    want, which the device flushes to 0, lands there too.
    BALANCED_ROUNDS bounds the unroll; an unconverged row leaves
    capacity unclaimed (the insensitivity truncation — still feasible,
    documented in doc/algorithms.md).
    Overload form only; the caller's fits-where handles underload."""
    dtype = wants.dtype
    zero = jnp.zeros((), dtype)
    one = jnp.ones((), dtype)
    tiny = jnp.finfo(dtype).tiny
    live0 = jnp.where(active, one, zero)

    def ratios(fixed, remcap):
        livef = jnp.where(fixed > 0, zero, live0)
        X = segsum(livef * weights)
        cap_ratio = X / jnp.maximum(remcap, tiny)
        ratio = jnp.where(
            (livef > 0) & (wants > 0),
            weights / jnp.maximum(wants, tiny),
            zero,
        )
        # Chunked segment_max yields the dtype minimum for empty
        # (padding) segments; ratios are >= 0, clamp.
        max_ratio = jnp.maximum(segmax(ratio), zero)
        return cap_ratio, ratio, max_ratio

    def body(_, carry):
        fixed, remcap = carry
        cap_ratio, ratio, max_ratio = ratios(fixed, remcap)
        peel = (ratio >= expand(max_ratio)) & expand(
            max_ratio > cap_ratio
        )
        fixed = jnp.where(peel, one, fixed)
        remcap = remcap - segsum(jnp.where(peel, wants, zero))
        return fixed, remcap

    fixed0 = jnp.where(wants > 0, zero, one)
    fixed, remcap = jax.lax.fori_loop(
        0, BALANCED_ROUNDS, body, (fixed0, capacity)
    )
    cap_ratio, _ratio, max_ratio = ratios(fixed, remcap)
    theta = jnp.maximum(cap_ratio, max_ratio)
    nu = one / jnp.maximum(theta, tiny)
    return jnp.where(
        fixed > 0, wants, jnp.minimum(wants, weights * expand(nu))
    )


def balanced_fill_compact(
    wants: jax.Array,  # [R, K], row layout
    weights: jax.Array,  # [R, K]
    active: jax.Array,  # [R, K] bool
    capacity: jax.Array,  # [R]
    rows: jax.Array,  # [F] int32 rows running this lane (repeats ok)
) -> jax.Array:
    """Row-layout balanced fill restricted to this lane's rows: gather,
    run the bounded recursion on the [F, K] subtable, scatter the GRANT
    rows back (the recursion's fixed mask is lease-shaped, so the
    scatter carries whole grant rows; duplicates write the same row).
    Non-selected rows read grant 0, which no lane consumes."""
    gets = balanced_fill(
        jnp.take(wants, rows, axis=0),
        jnp.take(weights, rows, axis=0),
        jnp.take(active, rows, axis=0),
        jnp.take(capacity, rows, axis=0),
        segsum=lambda v: v.sum(axis=1),
        segmax=lambda v: v.max(axis=1),
        expand=lambda totals: totals[:, None],
    )
    return jnp.zeros_like(wants).at[rows].set(gets, mode="drop")


def solve_lanes(
    wants: jax.Array,  # lease-shaped
    has: jax.Array,
    subclients: jax.Array,
    active: jax.Array,  # bool
    capacity: jax.Array,  # per-resource
    algo_kind: jax.Array,  # per-resource int
    learning: jax.Array,  # per-resource bool
    static_capacity: jax.Array,  # per-resource
    segsum: Reduce,
    segmax: Reduce,
    expand: Expand,
    lanes: "Optional[frozenset]" = None,
    fair_rows: "Optional[jax.Array]" = None,
    lane_rows: "Optional[dict]" = None,
) -> jax.Array:
    """Grants, lease-shaped; inactive lanes produce 0.

    `lanes`: the set of AlgoKind values PRESENT in `algo_kind` (host
    knowledge, e.g. the resident solver's config mirror). Lanes not in
    the set are skipped — byte-identical by construction, since the
    where-chain would never select them — which matters because the
    FAIR_SHARE water-fill alone costs ~50 full-table passes. None (the
    default, and what a caller without host kind knowledge must pass)
    computes every lane. The LEARN replay is always applied: learning is
    time-driven per tick, not part of the static kind set.

    `fair_rows`: row-layout callers (one row = one resource) may pass
    the FAIR_SHARE row indices to restrict the water-fill bisection to
    those rows (waterfill_level_compact — bit-identical per row).
    Ignored unless the FAIR_SHARE lane runs.

    `lane_rows`: the generalization of `fair_rows` to the whole
    iterative portfolio — {int(AlgoKind): [F] row indices} restricting
    each ITERATIVE_KINDS lane's fill to its own rows via the same
    compact gather→solve→scatter. `fair_rows` folds in as the
    FAIR_SHARE entry. Row-layout callers only; entries for lanes not
    in `lanes` are ignored."""
    dtype = wants.dtype
    zero = jnp.zeros((), dtype)
    tiny = jnp.finfo(dtype).tiny
    wants = jnp.where(active, wants, zero)
    has = jnp.where(active, has, zero)
    sub = jnp.where(active, subclients, zero)
    cap_e = expand(capacity)

    def need(kind_value) -> bool:
        return lanes is None or int(kind_value) in lanes

    rows_of = dict(lane_rows) if lane_rows else {}
    if fair_rows is not None:
        rows_of.setdefault(int(AlgoKind.FAIR_SHARE), fair_rows)

    sum_wants = segsum(wants)  # per-resource

    # ---- Lane: LEARN — replay the client's self-reported grant.
    gets_learn = has

    lane_outs = []

    # ---- Lane: NO_ALGORITHM — everyone gets what they want.
    if need(AlgoKind.NO_ALGORITHM):
        lane_outs.append((AlgoKind.NO_ALGORITHM, wants))

    # ---- Lane: STATIC — per-client configured cap.
    if need(AlgoKind.STATIC):
        lane_outs.append(
            (AlgoKind.STATIC, jnp.minimum(expand(static_capacity), wants))
        )

    # `free` feeds the proportional lanes; `fits` the topup/fair lanes.
    if need(AlgoKind.PROPORTIONAL_SHARE) or need(AlgoKind.PROPORTIONAL_TOPUP):
        free = jnp.maximum(cap_e - (expand(segsum(has)) - has), zero)
    if (
        need(AlgoKind.PROPORTIONAL_TOPUP)
        or need(AlgoKind.FAIR_SHARE)
        or need(AlgoKind.MAX_MIN_FAIR)
        or need(AlgoKind.BALANCED_FAIRNESS)
        or need(AlgoKind.PROPORTIONAL_FAIRNESS)
    ):
        fits = expand(sum_wants <= capacity)

    # ---- Lane: PROPORTIONAL_SHARE (simulation semantics,
    # algo_proportional.py:31-65): pure scaling by capacity / all_wants in
    # overload, clamped by the free capacity as seen from the snapshot
    # (own previous grant excluded from the outstanding-lease sum).
    if need(AlgoKind.PROPORTIONAL_SHARE):
        underloaded = expand(sum_wants < capacity)
        scaled = wants * (cap_e / expand(jnp.maximum(sum_wants, tiny)))
        lane_outs.append((
            AlgoKind.PROPORTIONAL_SHARE,
            jnp.where(
                underloaded,
                jnp.minimum(wants, free),
                jnp.minimum(scaled, free),
            ),
        ))

    # ---- Lane: FAIR_SHARE — full weighted max-min water-filling.
    if need(AlgoKind.FAIR_SHARE):
        fair = rows_of.get(int(AlgoKind.FAIR_SHARE))
        if fair is not None:
            level = waterfill_level_compact(
                wants, sub, active, capacity, fair
            )
        else:
            level = waterfill_level(
                wants, sub, active, capacity, segsum, segmax, expand
            )
        lane_outs.append((
            AlgoKind.FAIR_SHARE,
            jnp.where(fits, wants, jnp.minimum(wants, expand(level) * sub)),
        ))

    # ---- Lane: MAX_MIN_FAIR — client-granular (unweighted) max-min by
    # the fast-converging direct fill (arxiv 2310.09699; oracle
    # algorithms.tick.max_min_fair_tick).
    if need(AlgoKind.MAX_MIN_FAIR):
        ones = jnp.where(active, jnp.ones((), dtype), zero)
        rows = rows_of.get(int(AlgoKind.MAX_MIN_FAIR))
        if rows is not None:
            mm_level = iterfill_level_compact(wants, ones, capacity, rows)
        else:
            mm_level = iterfill_level(wants, ones, capacity, segsum, expand)
        lane_outs.append((
            AlgoKind.MAX_MIN_FAIR,
            jnp.where(
                fits, wants, jnp.minimum(wants, expand(mm_level) * ones)
            ),
        ))

    # ---- Lane: BALANCED_FAIRNESS — recursive cap-peeling shares
    # (arxiv 1711.02880; oracle algorithms.tick.balanced_fairness_tick).
    if need(AlgoKind.BALANCED_FAIRNESS):
        rows = rows_of.get(int(AlgoKind.BALANCED_FAIRNESS))
        if rows is not None:
            bal = balanced_fill_compact(wants, sub, active, capacity, rows)
        else:
            bal = balanced_fill(
                wants, sub, active, capacity, segsum, segmax, expand
            )
        lane_outs.append((
            AlgoKind.BALANCED_FAIRNESS, jnp.where(fits, wants, bal)
        ))

    # ---- Lane: PROPORTIONAL_FAIRNESS — Kelly log-utility dual
    # fixpoint, subclient-weighted (arxiv 1404.2266; oracle
    # algorithms.tick.proportional_fairness_tick).
    if need(AlgoKind.PROPORTIONAL_FAIRNESS):
        rows = rows_of.get(int(AlgoKind.PROPORTIONAL_FAIRNESS))
        if rows is not None:
            pf_level = iterfill_level_compact(wants, sub, capacity, rows)
        else:
            pf_level = iterfill_level(wants, sub, capacity, segsum, expand)
        lane_outs.append((
            AlgoKind.PROPORTIONAL_FAIRNESS,
            jnp.where(
                fits, wants, jnp.minimum(wants, expand(pf_level) * sub)
            ),
        ))

    # ---- Lane: PROPORTIONAL_TOPUP (Go semantics, snapshot form,
    # algorithm.go:213-292): equal share + top-up funded by clients under
    # their equal share.
    if need(AlgoKind.PROPORTIONAL_TOPUP):
        count = segsum(sub)
        equal = (cap_e / expand(jnp.maximum(count, tiny))) * sub
        under = wants < equal
        extra_capacity = expand(segsum(jnp.where(under, equal - wants, zero)))
        extra_need = expand(segsum(jnp.where(under, zero, wants - equal)))
        topped = equal + (wants - equal) * (
            extra_capacity / jnp.maximum(extra_need, tiny)
        )
        lane_outs.append((
            AlgoKind.PROPORTIONAL_TOPUP,
            jnp.where(
                fits | (wants <= equal),
                jnp.minimum(wants, free),
                jnp.minimum(topped, free),
            ),
        ))

    # A where-chain rather than jnp.select: identical semantics, and it
    # lowers on every backend pallas targets (select's argmax does not).
    # int(): an IntEnum operand becomes a strong-typed int64 scalar
    # const, which a pallas kernel body may not capture (it rejects any
    # non-ref closure constant); a Python int stays a weak-typed literal.
    kind_e = expand(algo_kind)
    gets = jnp.zeros_like(wants)
    for kind_value, lane in lane_outs:
        gets = jnp.where(kind_e == int(kind_value), lane, gets)
    # Learning-mode resources replay reported grants regardless of lane
    # (reference resource.go:108-111).
    gets = jnp.where(expand(learning), gets_learn, gets)
    return jnp.where(active, gets, zero)
