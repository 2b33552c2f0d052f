"""jit'd public wrappers for the Pallas kernels.

``interpret=None`` resolves by backend: the Pallas interpreter off-TPU
(how the CPU test suite runs the kernels) and the compiled Mosaic kernel on
a TPU.  Callers that must never fall back to the interpreter pass
``interpret=False`` (``chip_smoke.py``, ``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.selective_scan import selective_scan
from repro.kernels.maizx_rank import (MAX_TILE_K, block_nodes,
                                      maiz_lohi_pallas, maiz_lohi_pallas_b,
                                      maiz_topk_pallas, maiz_topk_pallas_b)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention_op(q, k, v, *, window: int = 0,
                       block_q: int = 128, block_k: int = 128,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Causal GQA flash attention: q (B,H,S,hd), k/v (B,K,S,hd)."""
    if interpret is None:
        interpret = _default_interpret()
    return flash_attention(q, k, v, window=window, block_q=block_q,
                           block_k=block_k, interpret=interpret)


def maiz_ranking_topk(ec, pue, ci_now, ci_fc, eff, sched, weights, *,
                      k: int = 16, lohi: Optional[jax.Array] = None,
                      pk: Optional[jax.Array] = None,
                      cap: Optional[jax.Array] = None,
                      chips_total: Optional[jax.Array] = None,
                      en: Optional[jax.Array] = None,
                      room: Optional[jax.Array] = None,
                      room_min: Optional[jax.Array] = None,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fleet-scale fused MAIZ ranking with a merged top-k shortlist.

    Arrays (N,) any float dtype; pads N up to a whole number of kernel
    blocks internally (``maizx_rank.block_nodes``; padded lanes are masked,
    never shortlisted).  Two memory-bound sweeps: a fused term+lo/hi
    pre-pass and the score+block-top-k pass; pass ``lohi``
    (R, 2) to pin the normalizers and skip sweep 1 (the placement engine
    freezes them per decision epoch).

    ``pk``/``cap``/``chips_total`` (node streams) + ``en`` ((4,) scalars
    ``[idle_frac, dyn_frac, embodied·horizon, w_marginal]``) thread the
    EnergyModel marginal-CFP term into the sweeps as a fifth score term
    (R = 5); omitted, the historical 4-term score is computed bit-exactly.
    With a traced ``en[3] == 0`` the fifth term adds ±0.0 — a bitwise
    no-op (see ``kernels.maizx_rank``).

    ``room_min`` (a scalar) masks every node whose ``room`` (N,) is below
    it, in the same pass: a masked node scores +inf in the returned
    ``scores`` too, not only in the top-k.  With the marginal streams the
    kernel compares ``cap`` and ``room`` is omitted.  Where fewer than k'
    nodes are unmasked, the tail of the top-k scores +inf and its node
    ids name no node: they may repeat or lie past N.

    Returns (scores (N,), topk_scores (k',), topk_nodes (k',)) with
    k' = min(k, N), ordered lexicographically by (score, node index) —
    identical tie-breaking to ``jnp.argmin`` / stable sort.

    Scan-compatible: the placement engine's epoch sweeps call this inside
    ``lax.scan`` (``simulator.simulate_fleet_scan`` with
    ``use_kernel=True``), in interpret mode on CPU and compiled on TPU.
    Callers embedding it in ``lax.cond`` branches should hoist it to the
    loop level where possible — XLA:CPU lowers the ``lax.top_k`` merge as
    a full sort inside conditionals (~50x slower; see the placement
    engine's ``eager_sweep``)."""
    if interpret is None:
        interpret = _default_interpret()
    n = ec.shape[0]
    k_out = min(k, n)
    k_tile = min(k_out, MAX_TILE_K)
    pad = block_nodes(n) - n

    def padded(x):
        return jnp.pad(x.astype(jnp.float32), (0, pad))

    args = tuple(padded(a) for a in (ec, pue, ci_now, ci_fc, eff, sched))
    mkw = {}
    if en is not None:
        mkw = dict(pk=padded(pk), cap=padded(cap), ct=padded(chips_total),
                   en=en)
    n_valid = jnp.full((1, 1), n, jnp.int32)
    if lohi is None:
        lohi = maiz_lohi_pallas(*args, n_valid, interpret=interpret, **mkw)
    scores, tmin, targ = maiz_topk_pallas(
        *args, n_valid, lohi, weights.astype(jnp.float32), k=k_tile,
        interpret=interpret, room_min=room_min,
        room=None if room is None else padded(room), **mkw)
    scores = scores[:n]
    if k_out > k_tile:
        # the block-local k is capped (unrolled extraction, MAX_TILE_K): a
        # single block could hold more than k_tile of the global top-k_out,
        # so merge from the full score vector instead — exact, same
        # lower-index tie rule, one extra O(N log k) host pass.
        neg, pos = jax.lax.top_k(-scores, k_out)
        return scores, -neg, pos.astype(jnp.int32)
    # merge block top-k's: candidates are (block, rank)-ordered, so
    # lax.top_k's lower-index-first tie rule preserves global (score, node)
    # order.
    neg, pos = jax.lax.top_k(-tmin.reshape(-1), k_out)
    return scores, -neg, targ.reshape(-1)[pos]


def maiz_ranking_topk_batched(ec, pue, ci_now, ci_fc, eff, sched, weights, *,
                              k: int = 16, lohi: Optional[jax.Array] = None,
                              pk: Optional[jax.Array] = None,
                              cap: Optional[jax.Array] = None,
                              chips_total: Optional[jax.Array] = None,
                              en: Optional[jax.Array] = None,
                              room: Optional[jax.Array] = None,
                              room_min: Optional[jax.Array] = None,
                              interpret: Optional[bool] = None,
                              mesh: Optional[jax.sharding.Mesh] = None
                              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched ``maiz_ranking_topk`` over a leading ensemble-lane axis.

    Node arrays (L, N), shared ``weights`` (4,), optional per-lane ``lohi``
    (L, R, 2), marginal streams (``pk``/``cap``/``chips_total`` (L, N),
    ``en`` (L, 4)) and room threshold (``room`` (L, N), ``room_min``
    (L,); masked nodes score +inf, as in ``maiz_ranking_topk``).  ONE
    (L × node-blocks)-grid kernel launch scores every lane; per-lane block
    candidates are merged by one batched ``lax.top_k``.
    Each lane's (scores, topk_scores, topk_nodes) is identical to the
    sequential ``maiz_ranking_topk`` on that lane — the round-boundary
    sweep of ``placement.place_lifecycle_batched`` relies on this for
    ensemble/scan-driver parity.

    ``mesh`` (an ``("e",)`` or ``("e", "n")`` mesh, see
    ``distributed.sharding.ensemble_mesh``) runs the sweep per device
    under ``shard_map``: XLA cannot partition a compiled Pallas kernel
    itself.  Lanes split over ``"e"`` and nodes over ``"n"``; each
    node shard merges its own candidates, and one more ``lax.top_k`` over
    the shards' candidates (in node order) gives the same shortlist as
    one device.  Needs ``lohi``: normalizers are global, not per shard."""
    if mesh is not None:
        return _topk_batched_sharded(
            mesh, (ec, pue, ci_now, ci_fc, eff, sched), weights, k, lohi,
            dict(pk=pk, cap=cap, chips_total=chips_total, en=en, room=room,
                 room_min=room_min), interpret)
    if interpret is None:
        interpret = _default_interpret()
    L, n = ec.shape
    k_out = min(k, n)
    k_tile = min(k_out, MAX_TILE_K)
    pad = block_nodes(n) - n

    def padded(x):
        return jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad)))

    args = tuple(padded(a) for a in (ec, pue, ci_now, ci_fc, eff, sched))
    mkw = {}
    if en is not None:
        mkw = dict(pk=padded(pk), cap=padded(cap), ct=padded(chips_total),
                   en=en)
    n_valid = jnp.full((1, 1), n, jnp.int32)
    if lohi is None:
        lohi = maiz_lohi_pallas_b(*args, n_valid, interpret=interpret, **mkw)
    scores, tmin, targ = maiz_topk_pallas_b(
        *args, n_valid, lohi, weights.astype(jnp.float32), k=k_tile,
        interpret=interpret, room_min=room_min,
        room=None if room is None else padded(room), **mkw)
    scores = scores[:, :n]
    if k_out > k_tile:
        # same oversized-shortlist fallback as the sequential wrapper,
        # batched along the lane axis (lax.top_k reduces the last dim)
        neg, pos = jax.lax.top_k(-scores, k_out)
        return scores, -neg, pos.astype(jnp.int32)
    neg, pos = jax.lax.top_k(-tmin.reshape(L, -1), k_out)
    return scores, -neg, jnp.take_along_axis(targ.reshape(L, -1), pos, axis=1)


def _topk_batched_sharded(mesh, streams, weights, k, lohi, marginal,
                          interpret):
    """``maiz_ranking_topk_batched`` split over ``mesh`` (see there)."""
    from jax.sharding import PartitionSpec as P
    if lohi is None:
        raise ValueError("a sharded sweep needs the frozen global lohi")
    split_n = "n" in mesh.axis_names and mesh.shape["n"] > 1
    node_spec = P("e", "n") if split_n else P("e")
    names = [k_ for k_, v in marginal.items() if v is not None]
    ops_ = list(streams) + [marginal[k_] for k_ in names] + [lohi, weights]
    per_lane = ("en", "room_min")
    specs = ([node_spec] * len(streams)
             + [P("e") if k_ in per_lane else node_spec for k_ in names]
             + [P("e"), P()])
    n_local = streams[0].shape[1] // (mesh.shape["n"] if split_n else 1)

    def local(*a):
        mkw = dict(zip(names, a[len(streams):-2]))
        scores, cs, ci = maiz_ranking_topk_batched(
            *a[:len(streams)], a[-1], k=k, lohi=a[-2], interpret=interpret,
            **mkw)
        if split_n:
            ci = ci + jax.lax.axis_index("n") * n_local
        return scores, cs, ci

    scores, cs, ci = jax.shard_map(
        local, mesh=mesh, in_specs=tuple(specs),
        out_specs=(node_spec, node_spec, node_spec), check_vma=False)(*ops_)
    if split_n:
        # shards are contiguous node ranges, each list (score, node)-sorted:
        # lax.top_k's lower-position-first tie rule keeps the global order
        neg, pos = jax.lax.top_k(-cs, min(k, streams[0].shape[1]))
        cs, ci = -neg, jnp.take_along_axis(ci, pos, axis=1)
    return scores, cs, ci


def maiz_ranking_fused(ec, pue, ci_now, ci_fc, eff, sched, weights, *,
                       interpret: Optional[bool] = None
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fleet-scale fused MAIZ ranking (k=1 shortlist).

    Returns (scores (N,), best_score, best_node)."""
    scores, top_s, top_i = maiz_ranking_topk(
        ec, pue, ci_now, ci_fc, eff, sched, weights, k=1,
        interpret=interpret)
    return scores, top_s[0], top_i[0]


def selective_scan_op(dt, x, b, c, a, *, block_d: int = 128,
                      q_chunk: int = 16,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Mamba-1 selective scan (VMEM-resident state; see kernel docstring)."""
    if interpret is None:
        interpret = _default_interpret()
    return selective_scan(dt, x, b, c, a, block_d=block_d, q_chunk=q_chunk,
                          interpret=interpret)
