"""Pallas TPU kernels: fused fleet-scale MAIZ_RANKING (Eq. 2 + Eq. 1 + top-k).

The paper ranks 3 nodes in a Python loop; at 10^5..10^6 schedulable nodes the
scoring pass is a memory-streaming problem.  The TPU adaptation is two
memory-bound sweeps over the node axis, each touching every input stream
exactly once:

sweep 1 (``_lohi_kernel``)  — per (8, 128) VMEM tile, compute the Eq. 1
    terms and reduce their tile-local (lo, hi); the wrapper folds the
    per-tile partials into the global (R, 2) min-max normalizers.

sweep 2 (``_topk_kernel``) — per block of B tiles ((B·8, 128) in VMEM):

    cf   = ec · pue · ci_now          (Eq. 2, current)
    fcf  = ec · pue · ci_fc           (Eq. 2, forecast)
    score = w1·n(cf) + w2·n(fcf) + w3·(1 − n(eff)) + w4·n(sched)   (Eq. 1)
    [+ w_m·n(mcfp) when the EnergyModel scalars are threaded in — see below]
    block-local top-k (scores + global indices) by iterative min-extraction

where n(·) is min-max normalization with the sweep-1 lo/hi.  Each
extraction step folds the block's vregs elementwise and then waits on a
chain of two cross-lane reductions, whose latency does not grow with the
block, so the block is as large as the shape allows (``block_tiles``):
nt tiles split into nb = ceil(nt / MAX_BLOCK_TILES) blocks of B =
ceil(nt / nb) tiles, one grid step each.
The block top-k's are merged after the kernel by one ``lax.top_k`` over
nb·k candidates, giving the exact global shortlist the placement engine
(``repro.core.placement``) consumes.  Ties break toward the lower node
index at every stage (extraction order within a block, block order
across blocks, ``lax.top_k`` stability), so the merged shortlist is the
lexicographic (score, index) head — identical to ``jnp.argmin`` /
stable-sort semantics.

**Generalized score (EnergyModel + marginal CFP).**  The historical kernel
baked the four-term score; both sweeps now optionally accept three extra
node streams — ``pk`` (full-load power·horizon), ``cap`` (free chips, f32)
and ``ct`` (total chips, f32) — plus one (1, 4) VMEM block
``en = [idle_frac, dyn_frac, embodied·horizon, w_marginal]``.  When present,
the kernels compute the Eq. 1 marginal-CFP term in-tile with the same op
order as ``placement.frozen_ctx`` (``a_now = (pk·pue)·ci``, per-chip dynamic
carbon for running nodes, idle + embodied wake price charged only to fully
idle ones) and add ``w_m · n(mcfp)`` as a fifth term.  Select-then-add keeps
a traced ``w_m == 0`` a bitwise no-op, so the default model reproduces the
historical 4-term scores exactly.  Custom idle/dynamic watts need no kernel
change at all: they flow through the caller-computed ``ec`` stream
(``Fleet.effective_power_kw(cap, energy=...)``).

**Batched lane axis.**  ``maiz_lohi_pallas_b``/``maiz_topk_pallas_b`` are
the (L, N) twins on a 2D (lane × tile) grid, (lane × block) for sweep 2 —
ONE kernel launch per ensemble round instead of L — used by
``placement.place_lifecycle_batched`` for
``simulate_fleet_ensemble(use_kernel=True)``.  Per-lane blocks are the
same (8, 128) tiles and (B·8, 128) blocks, so each lane's
scores/candidates are identical to the sequential kernels run on that
lane.

Padding: arrays are padded up to a whole number of blocks
(``block_nodes``); a scalar ``n_valid`` masks padded lanes out of both the
lo/hi reduction and the score output (padded scores are +inf, so they can
never enter a shortlist).  Sweep 2 optionally widens that mask by a room
threshold: a node whose free-chip ``room`` is below the scalar
``room_min`` scores +inf as well (the placement engine's eligibility rule;
with the marginal streams the ``cap`` stream it already reads is the
room, so no stream is added).

**Output layout.**  Mosaic accepts only (8, 128)-aligned blocks (or whole
arrays), so every per-step result — the per-tile (lo, hi) pairs of sweep
1, the per-block top-k scores and node ids of sweep 2 — is assembled in
registers into one lane-dense (8, 128) tile (``_put``) and stored once per
grid step; the wrappers fold or slice those tiles (``_fold_lohi``,
``_block_cands``).

``repro.kernels.ref.maiz_ranking_ref`` is the pure-jnp oracle;
``repro.core.ranking.maiz_ranking`` is the paper-faithful module
implementation both are tested against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
TILE = LANES * SUBLANES
# the per-block top-k is an UNROLLED min-extraction (O(k·B·TILE) work and
# k unrolled steps to compile), so block-local k is capped; larger
# shortlists are merged host-side from the full score vector (see
# ops.maiz_ranking_topk)
MAX_TILE_K = 64
# sweep 2's largest block, in tiles: one block a lane for the benchmark's
# 12- and 36-tile fleets, and a whole number of blocks for power-of-two
# shards (512 tiles: 8 x 64); ten double-buffered f32 streams of 64 tiles
# take 5 MiB of the v5e's 16 MiB scoped VMEM
MAX_BLOCK_TILES = 64
_BIG = 3e38        # finite sentinel for masked min/max (below f32 max)


def _check_tile_k(k: int) -> None:
    if not 1 <= k <= MAX_TILE_K:
        raise ValueError(
            f"block-local top-k k={k} is outside [1, MAX_TILE_K={MAX_TILE_K}]"
            " — the in-kernel min-extraction is unrolled k times, so the"
            " per-block candidate list is capped.  Either shrink the"
            f" shortlist (placement needs k = shortlist + 1 <= {MAX_TILE_K})"
            " or call repro.kernels.ops.maiz_ranking_topk, which merges"
            " oversized shortlists host-side from the full score vector.")


def block_tiles(nt: int) -> tuple[int, int]:
    """Sweep 2's (nb, B) for ``nt`` node tiles: the fewest blocks of at
    most ``MAX_BLOCK_TILES`` tiles, B = ceil(nt / nb) tiles each."""
    nb = -(-max(nt, 1) // MAX_BLOCK_TILES)
    return nb, -(-max(nt, 1) // nb)


def block_nodes(n: int) -> int:
    """``n`` nodes padded up to sweep 2's whole number of blocks."""
    nb, b = block_tiles(-(-n // TILE))
    return nb * b * TILE


def _flat_ids(rows=SUBLANES):
    """Block-local flat node ids of ``rows`` sublane rows (tile · 1024 +
    row · 128 + lane), TPU-safe (2D iota)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    return row * LANES + col


def _tile_terms(ec, pue, ci, fc, eff, sw):
    """The four historical Eq. 1 terms for one node tile or block."""
    ec = ec.astype(jnp.float32)
    pue = pue.astype(jnp.float32)
    base = ec * pue
    cf = base * ci.astype(jnp.float32)
    fcf = base * fc.astype(jnp.float32)
    return [cf, fcf, eff.astype(jnp.float32), sw.astype(jnp.float32)]


def _tile_mcfp(pk, pue, ci, cap, ct, en):
    """Eq. 1 marginal-CFP term for one node tile or block.

    Mirrors ``placement.frozen_ctx`` op-for-op (same association order) so
    the in-kernel term carries the same f32 values the jnp engines score
    with: ``a_now = (pk·pue)·ci``; per-chip dynamic carbon for running
    nodes; the idle-floor + amortized-embodied wake price charged only to
    fully idle ones.  ``en = [idle_frac, dyn_frac, embodied·horizon, w_m]``
    lives in a (1, 4) VMEM block."""
    an = pk.astype(jnp.float32) * pue.astype(jnp.float32)
    an = an * ci.astype(jnp.float32)
    ct = ct.astype(jnp.float32)
    inv = 1.0 / jnp.maximum(ct, 1.0)
    m_dyn = an * inv * en[0, 1]
    m_wake = an * en[0, 0] + en[0, 2]
    return m_dyn + jnp.where(cap.astype(jnp.float32) == ct, m_wake, 0.0)


def _tile_score(terms, lohi, w, w5):
    """Weighted normalized Eq. 1 score for one block; ``w5`` is the traced
    marginal weight (None -> historical 4-term score)."""

    def norm(x, i):
        # degenerate span -> 0 contribution (matches ranking._minmax); the
        # reciprocal form also keeps the ulp-level FMA difference between
        # this pass's terms and sweep-1's lo from being amplified by 1e12
        lo, hi = lohi[i, 0], lohi[i, 1]
        span = hi - lo
        rcp = jnp.where(span > 1e-12, 1.0 / jnp.maximum(span, 1e-12), 0.0)
        return (x - lo) * rcp

    score = (w[0, 0] * norm(terms[0], 0) + w[0, 1] * norm(terms[1], 1)
             + w[0, 2] * (1.0 - norm(terms[2], 2)) + w[0, 3] * norm(terms[3], 3))
    if w5 is not None:
        # select-then-add: with traced w5 == 0 this adds ±0.0, a bitwise
        # no-op — the same discipline as placement._ctx_scores
        score = score + w5 * norm(terms[4], 4)
    return score


def _block_topk(score, fids, k, block_base):
    """Unrolled min-extraction over a whole (B·8, 128) block: k is small and
    static, keeping everything 2D and avoiding dynamic ref indexing.  Each
    step is one block min (elementwise across the block's vregs, then one
    cross-lane reduction) and the lowest block-local id holding it, so
    equal scores yield the lower node first, matching jnp.argmin's
    first-occurrence rule.  Returns the lane-dense (tmin, targ) tiles:
    candidate ``kk`` sits in row 0, lane ``kk`` (see ``_put``)."""
    slots = _flat_ids()
    tmin = jnp.zeros((SUBLANES, LANES), jnp.float32)
    targ = jnp.zeros((SUBLANES, LANES), jnp.int32)
    cur = score
    for kk in range(k):
        m = jnp.min(cur)
        pos = jnp.min(jnp.where(cur == m, fids, score.size))
        tmin = _put(tmin, slots, kk, m)
        targ = _put(targ, slots, kk, pos + block_base)
        cur = jnp.where(fids == pos, jnp.inf, cur)
    return tmin, targ


def _put(tile, fids, slot, v):
    """Select scalar ``v`` into flat slot ``slot`` (row ``slot // 128``,
    lane ``slot % 128``) of an (8, 128) tile.  Per-step results are
    assembled this way and stored as one whole tile per grid step, the
    only output block shape Mosaic accepts for such a result."""
    return jnp.where(fids == slot, v, tile)


def _lohi_tile(terms, valid, fids):
    """Tile-local (lo, hi) of every term: lo of term i in row 0, lane i;
    hi in row 1, lane i (read back by ``_fold_lohi``)."""
    out = jnp.zeros((SUBLANES, LANES), jnp.float32)
    for i, t in enumerate(terms):
        out = _put(out, fids, i, jnp.min(jnp.where(valid, t, _BIG)))
        out = _put(out, fids, LANES + i, jnp.max(jnp.where(valid, t, -_BIG)))
    return out


def _read_terms(ec_ref, pue_ref, ci_ref, fc_ref, eff_ref, sw_ref, rest,
                n_extra, lane=None):
    """Shared ref unpacking for both grid layouts: returns (terms, w5).
    ``rest[:4] = (pk, cap, ct, en)`` refs when the marginal streams are
    threaded in (``n_extra`` trailing refs are outputs/lohi/weights)."""
    rd = (lambda r: r[...]) if lane is None else (lambda r: r[lane])
    terms = _tile_terms(rd(ec_ref), rd(pue_ref), rd(ci_ref), rd(fc_ref),
                        rd(eff_ref), rd(sw_ref))
    w5 = None
    if len(rest) > n_extra:
        pk_ref, cap_ref, ct_ref, en_ref = rest[:4]
        en = rd(en_ref)
        terms.append(_tile_mcfp(rd(pk_ref), rd(pue_ref), rd(ci_ref),
                                rd(cap_ref), rd(ct_ref), en))
        w5 = en[0, 3]
    return terms, w5


def _lohi_kernel(n_ref, ec_ref, pue_ref, ci_ref, fc_ref, eff_ref, sw_ref,
                 *rest):
    out_ref = rest[-1]
    terms, _ = _read_terms(ec_ref, pue_ref, ci_ref, fc_ref, eff_ref, sw_ref,
                           rest, 1)
    fids = _flat_ids()
    valid = fids + pl.program_id(0) * TILE < n_ref[0, 0]
    out_ref[...] = _lohi_tile(terms, valid, fids)


def _room_valid(valid, rest, n_room, rd):
    """Widen the padded-tail mask by the room threshold.  ``n_room`` refs
    sit just before the lo/hi block: none (no threshold), the (1, 1)
    ``room_min`` scalar alone (the marginal ``cap`` stream, ``rest[1]``,
    is the room), or the room stream and then ``room_min``."""
    if not n_room:
        return valid
    room_ref = rest[-7] if n_room == 2 else rest[1]
    return valid & (rd(room_ref) >= rd(rest[-6])[0, 0])


def _topk_kernel(n_ref, ec_ref, pue_ref, ci_ref, fc_ref, eff_ref, sw_ref,
                 *rest, k: int, n_room: int, bt: int):
    lohi_ref, w_ref, score_ref, tmin_ref, targ_ref = rest[-5:]
    base = pl.program_id(0) * (bt * TILE)
    fids = _flat_ids(bt * SUBLANES)
    valid = _room_valid(fids + base < n_ref[0, 0], rest, n_room,
                        lambda r: r[...])
    terms, w5 = _read_terms(ec_ref, pue_ref, ci_ref, fc_ref, eff_ref, sw_ref,
                            rest, 5 + n_room)
    score = _tile_score(terms, lohi_ref[...], w_ref[...], w5)
    score = jnp.where(valid, score, jnp.inf)
    score_ref[...] = score
    tmin_ref[...], targ_ref[...] = _block_topk(score, fids, k, base)


def _lohi_kernel_b(n_ref, ec_ref, pue_ref, ci_ref, fc_ref, eff_ref, sw_ref,
                   *rest):
    """Batched twin on a (lane, tile) grid; every per-lane ref carries a
    leading unit lane-block axis that ``_read_terms`` peels off."""
    out_ref = rest[-1]
    terms, _ = _read_terms(ec_ref, pue_ref, ci_ref, fc_ref, eff_ref, sw_ref,
                           rest, 1, lane=0)
    fids = _flat_ids()
    valid = fids + pl.program_id(1) * TILE < n_ref[0, 0]
    out_ref[0] = _lohi_tile(terms, valid, fids)


def _topk_kernel_b(n_ref, ec_ref, pue_ref, ci_ref, fc_ref, eff_ref, sw_ref,
                   *rest, k: int, n_room: int, bt: int):
    lohi_ref, w_ref, score_ref, tmin_ref, targ_ref = rest[-5:]
    base = pl.program_id(1) * (bt * TILE)
    fids = _flat_ids(bt * SUBLANES)
    valid = _room_valid(fids + base < n_ref[0, 0], rest, n_room,
                        lambda r: r[0])
    terms, w5 = _read_terms(ec_ref, pue_ref, ci_ref, fc_ref, eff_ref, sw_ref,
                            rest, 5 + n_room, lane=0)
    score = _tile_score(terms, lohi_ref[0], w_ref[...], w5)
    score = jnp.where(valid, score, jnp.inf)
    score_ref[0] = score
    tmin_ref[0], targ_ref[0] = _block_topk(score, fids, k, base)


def _fold_lohi(tiles, nt, r):
    """Fold the per-tile ``_lohi_tile`` outputs ((..., nt·8, 128)) into
    the global (..., R, 2) normalizers."""
    t = tiles.reshape(*tiles.shape[:-2], nt, SUBLANES, LANES)
    return jnp.stack([t[..., 0, :r].min(-2), t[..., 1, :r].max(-2)], axis=-1)


def _block_cands(tiles, nb, k):
    """The (..., nb, k) block top-k candidates from row 0 of each per-block
    ``_block_topk`` output tile."""
    return tiles.reshape(*tiles.shape[:-2], nb, SUBLANES, LANES)[..., 0, :k]


def _node_args(arrs, nt):
    shape2d = (nt * SUBLANES, LANES)
    return [a.reshape(shape2d) for a in arrs], shape2d


_NODE_SPEC = pl.BlockSpec((SUBLANES, LANES), lambda t: (t, 0))
_SCALAR_SPEC = pl.BlockSpec((1, 1), lambda t: (0, 0))
# batched twins: (lane, tile) grid, unit lane block
_NODE_SPEC_B = pl.BlockSpec((1, SUBLANES, LANES), lambda l, t: (l, t, 0))
_SCALAR_SPEC_B = pl.BlockSpec((1, 1), lambda l, t: (0, 0))


def _block_spec(bt, per_lane=False):
    """Sweep 2's node block of ``bt`` tiles, (lane,) block grid."""
    if per_lane:
        return pl.BlockSpec((1, bt * SUBLANES, LANES),
                            lambda l, b: (l, b, 0))
    return pl.BlockSpec((bt * SUBLANES, LANES), lambda b: (b, 0))


def _check_blocks(n):
    """(nb, B) of sweep 2 for ``n`` nodes, which must be whole blocks."""
    if block_nodes(n) != n:
        raise ValueError(
            f"sweep 2 takes whole blocks: pad the node axis from {n} to"
            f" block_nodes({n}) = {block_nodes(n)}")
    return block_tiles(n // TILE)


def _marginal_ops(marginal, en, per_lane=False):
    """(extra in_specs, extra operands) for the threaded EnergyModel block."""
    if not marginal:
        return [], []
    if per_lane:
        L = en.shape[0]
        return ([pl.BlockSpec((1, 1, 4), lambda l, t: (l, 0, 0))],
                [en.reshape(L, 1, 4).astype(jnp.float32)])
    return ([pl.BlockSpec((1, 4), lambda t: (0, 0))],
            [en.reshape(1, 4).astype(jnp.float32)])


def _room_ops(room, room_min, marginal, shape, node_spec, per_lane=False):
    """(extra in_specs, extra operands, n_room) for the room threshold:
    the room stream (unless the marginal ``cap`` stream serves as the
    room) and the ``room_min`` scalar, one per lane when batched."""
    if room_min is None:
        return [], [], 0
    if per_lane:
        L = shape[0]
        specs = [pl.BlockSpec((1, 1, 1), lambda l, t: (l, 0, 0))]
        ops = [room_min.reshape(L, 1, 1).astype(jnp.float32)]
    else:
        specs = [pl.BlockSpec((1, 1), lambda t: (0, 0))]
        ops = [room_min.reshape(1, 1).astype(jnp.float32)]
    if marginal:
        if room is not None:
            raise ValueError("with the marginal streams the kernel reads "
                             "`cap` as the room: pass no `room`")
        return specs, ops, 1
    return ([node_spec] + specs,
            [room.astype(jnp.float32).reshape(shape)] + ops, 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def maiz_lohi_pallas(ec, pue, ci_now, ci_fc, eff, sched, n_valid, *,
                     pk=None, cap=None, ct=None, en=None,
                     interpret: bool = False):
    """Sweep 1: global (R, 2) term lo/hi.  Node arrays (N,), N % 1024 == 0;
    ``n_valid`` (1, 1) int32 masks the padded tail.  R = 5 with the
    marginal streams (``pk``/``cap``/``ct``/``en``), else 4."""
    n = ec.shape[0]
    assert n % TILE == 0, n
    nt = n // TILE
    marginal = en is not None
    arrs = [ec, pue, ci_now, ci_fc, eff, sched]
    if marginal:
        arrs += [pk, cap, ct]
    args, shape2d = _node_args(arrs, nt)
    en_specs, en_ops = _marginal_ops(marginal, en)
    r = 5 if marginal else 4
    tiles = pl.pallas_call(
        _lohi_kernel,
        grid=(nt,),
        in_specs=[_SCALAR_SPEC] + [_NODE_SPEC] * len(args) + en_specs,
        out_specs=_NODE_SPEC,
        out_shape=jax.ShapeDtypeStruct(shape2d, jnp.float32),
        interpret=interpret,
    )(n_valid, *args, *en_ops)
    return _fold_lohi(tiles, nt, r)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def maiz_topk_pallas(ec, pue, ci_now, ci_fc, eff, sched, n_valid, lohi,
                     weights, *, k: int, pk=None, cap=None, ct=None, en=None,
                     room=None, room_min=None, interpret: bool = False):
    """Sweep 2: scores + per-block top-k.  Node arrays (N,), N a whole
    number of blocks (``block_nodes(N) == N``).  Returns (scores (N,)
    with +inf in the padded tail, block_topk_scores (nb, k),
    block_topk_idx (nb, k)).  With ``room_min`` (a scalar), nodes whose
    ``room`` (N,) is below it score +inf too (see
    ``ops.maiz_ranking_topk``); with the marginal streams ``cap`` is the
    room and ``room`` is omitted."""
    n = ec.shape[0]
    _check_tile_k(k)
    nb, bt = _check_blocks(n)
    marginal = en is not None
    r = 5 if marginal else 4
    assert lohi.shape[0] == r, (lohi.shape, r)
    arrs = [ec, pue, ci_now, ci_fc, eff, sched]
    if marginal:
        arrs += [pk, cap, ct]
    args, shape2d = _node_args(arrs, n // TILE)
    node_spec = _block_spec(bt)
    en_specs, en_ops = _marginal_ops(marginal, en)
    room_specs, room_ops, n_room = _room_ops(room, room_min, marginal,
                                             shape2d, node_spec)
    cand_shape = (nb * SUBLANES, LANES)
    scores, tmin, targ = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, n_room=n_room, bt=bt),
        grid=(nb,),
        in_specs=[_SCALAR_SPEC] + [node_spec] * len(args) + en_specs
        + room_specs + [
            pl.BlockSpec((r, 2), lambda b: (0, 0)),      # lo/hi
            pl.BlockSpec((1, 4), lambda b: (0, 0)),      # weights
        ],
        out_specs=[node_spec, _NODE_SPEC, _NODE_SPEC],
        out_shape=[
            jax.ShapeDtypeStruct(shape2d, jnp.float32),
            jax.ShapeDtypeStruct(cand_shape, jnp.float32),
            jax.ShapeDtypeStruct(cand_shape, jnp.int32),
        ],
        interpret=interpret,
    )(n_valid, *args, *en_ops, *room_ops, lohi, weights.reshape(1, 4))
    return (scores.reshape(n), _block_cands(tmin, nb, k),
            _block_cands(targ, nb, k))


@functools.partial(jax.jit, static_argnames=("interpret",))
def maiz_lohi_pallas_b(ec, pue, ci_now, ci_fc, eff, sched, n_valid, *,
                       pk=None, cap=None, ct=None, en=None,
                       interpret: bool = False):
    """Batched sweep 1 over a leading lane axis: node arrays (L, N) with
    N % 1024 == 0, ``en`` (L, 4).  ONE launch on an (L, nt) grid; returns
    the per-lane (L, R, 2) lo/hi."""
    L, n = ec.shape
    assert n % TILE == 0, n
    nt = n // TILE
    marginal = en is not None
    arrs = [ec, pue, ci_now, ci_fc, eff, sched]
    if marginal:
        arrs += [pk, cap, ct]
    args = [a.reshape(L, nt * SUBLANES, LANES) for a in arrs]
    en_specs, en_ops = _marginal_ops(marginal, en, per_lane=True)
    r = 5 if marginal else 4
    tiles = pl.pallas_call(
        _lohi_kernel_b,
        grid=(L, nt),
        in_specs=[_SCALAR_SPEC_B] + [_NODE_SPEC_B] * len(args) + en_specs,
        out_specs=_NODE_SPEC_B,
        out_shape=jax.ShapeDtypeStruct((L, nt * SUBLANES, LANES),
                                       jnp.float32),
        interpret=interpret,
    )(n_valid, *args, *en_ops)
    return _fold_lohi(tiles, nt, r)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def maiz_topk_pallas_b(ec, pue, ci_now, ci_fc, eff, sched, n_valid, lohi,
                       weights, *, k: int, pk=None, cap=None, ct=None,
                       en=None, room=None, room_min=None,
                       interpret: bool = False):
    """Batched sweep 2: node arrays (L, N), N a whole number of blocks,
    ``lohi`` (L, R, 2), shared ``weights`` (4,), ``en`` (L, 4),
    ``room_min`` (L,).  Returns (scores (L, N), tmin (L, nb, k), targ (L,
    nb, k)) from ONE (L, nb)-grid launch; each lane is identical to the
    sequential kernel run on that lane."""
    L, n = ec.shape
    _check_tile_k(k)
    nb, bt = _check_blocks(n)
    nt = n // TILE
    marginal = en is not None
    r = 5 if marginal else 4
    assert lohi.shape[1:] == (r, 2), (lohi.shape, r)
    arrs = [ec, pue, ci_now, ci_fc, eff, sched]
    if marginal:
        arrs += [pk, cap, ct]
    shape = (L, nt * SUBLANES, LANES)
    args = [a.reshape(shape) for a in arrs]
    node_spec = _block_spec(bt, per_lane=True)
    en_specs, en_ops = _marginal_ops(marginal, en, per_lane=True)
    room_specs, room_ops, n_room = _room_ops(
        room, room_min, marginal, shape, node_spec, per_lane=True)
    cand_shape = (L, nb * SUBLANES, LANES)
    scores, tmin, targ = pl.pallas_call(
        functools.partial(_topk_kernel_b, k=k, n_room=n_room, bt=bt),
        grid=(L, nb),
        in_specs=[_SCALAR_SPEC_B] + [node_spec] * len(args) + en_specs
        + room_specs + [
            pl.BlockSpec((1, r, 2), lambda l, b: (l, 0, 0)),   # lo/hi
            pl.BlockSpec((1, 4), lambda l, b: (0, 0)),         # weights
        ],
        out_specs=[node_spec, _NODE_SPEC_B, _NODE_SPEC_B],
        out_shape=[
            jax.ShapeDtypeStruct(shape, jnp.float32),
            jax.ShapeDtypeStruct(cand_shape, jnp.float32),
            jax.ShapeDtypeStruct(cand_shape, jnp.int32),
        ],
        interpret=interpret,
    )(n_valid, *args, *en_ops, *room_ops, lohi, weights.reshape(1, 4))
    return (scores.reshape(L, n), _block_cands(tmin, nb, k),
            _block_cands(targ, nb, k))
