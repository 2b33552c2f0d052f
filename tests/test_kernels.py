"""Pallas kernels vs pure-jnp oracles (interpret mode), sweeping shapes and
dtypes per the brief."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.maizx_rank import block_tiles
from repro.kernels.ops import (flash_attention_op, maiz_ranking_fused,
                               maiz_ranking_topk, maiz_ranking_topk_batched,
                               selective_scan_op)

FLASH_CASES = [
    # (B, H, K, S, hd, window, dtype)
    (2, 4, 4, 256, 64, 0, jnp.float32),      # MHA
    (1, 8, 2, 128, 128, 0, jnp.bfloat16),    # GQA 4:1
    (2, 4, 1, 256, 64, 0, jnp.float32),      # MQA
    (2, 4, 4, 256, 64, 128, jnp.float32),    # sliding window
    (1, 2, 2, 384, 128, 0, jnp.bfloat16),    # non-pow2 block count
    (1, 4, 2, 512, 32, 256, jnp.bfloat16),   # small head dim + window
]


@pytest.mark.parametrize("case", FLASH_CASES,
                         ids=[f"B{c[0]}H{c[1]}K{c[2]}S{c[3]}hd{c[4]}w{c[5]}"
                              f"{c[6].__name__}" for c in FLASH_CASES])
def test_flash_attention_matches_ref(case, rng):
    B, H, K, S, hd, win, dt = case
    q = jnp.asarray(rng.standard_normal((B, H, S, hd)), dt)
    k = jnp.asarray(rng.standard_normal((B, K, S, hd)), dt)
    v = jnp.asarray(rng.standard_normal((B, K, S, hd)), dt)
    out = flash_attention_op(q, k, v, window=win, interpret=True)
    want = ref.attention_ref(q, k, v, window=win)
    tol = 5e-6 if dt == jnp.float32 else 6e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("blocks", [(64, 64), (128, 256), (256, 128)])
def test_flash_attention_block_shape_invariance(blocks, rng):
    bq, bk = blocks
    q = jnp.asarray(rng.standard_normal((1, 4, 512, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    out = flash_attention_op(q, k, v, block_q=bq, block_k=bk, interpret=True)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


# sweep-2 node counts: one block (ragged), one 38-tile block (ragged), and
# past MAX_BLOCK_TILES tiles: 130 tiles in three blocks of 44, the last
# padded by two tiles and the ragged tail
ONE_BLOCK, WIDE_BLOCK, MULTI_BLOCK = 2000, 37 * 1024 + 5, 129 * 1024 + 5


@pytest.mark.parametrize("n", [1024, 2048, 4096, 1000, 5000, WIDE_BLOCK,
                               MULTI_BLOCK])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_maiz_ranking_kernel_matches_ref(n, dtype, rng):
    ec = jnp.asarray(rng.random(n) * 100, dtype)
    pue = jnp.asarray(1 + rng.random(n), dtype)
    ci = jnp.asarray(rng.random(n) * 500, dtype)
    fc = jnp.asarray(rng.random(n) * 500, dtype)
    eff = jnp.asarray(rng.random(n), dtype)
    sw = jnp.asarray(rng.random(n), dtype)
    w = jnp.asarray([0.35, 0.25, 0.25, 0.15], jnp.float32)
    scores, best_s, best_n = maiz_ranking_fused(ec, pue, ci, fc, eff, sw, w,
                                                interpret=True)
    lohi = ref.term_lohi(ec, pue, ci, fc, eff, sw)
    want, want_min, want_arg = ref.maiz_ranking_ref(
        ec, pue, ci, fc, eff, sw, lohi, w)
    np.testing.assert_allclose(np.asarray(scores), np.asarray(want),
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)
    # argmin must agree exactly in f32; in bf16 scores can tie — accept any
    # node whose oracle score is within quantization of the oracle minimum
    if dtype == jnp.float32:
        assert int(best_n) == int(want_arg)
    else:
        assert float(want[int(best_n)]) <= float(want_min) + 2e-2


def test_maiz_ranking_kernel_matches_module_implementation(rng):
    """Kernel == the paper-faithful repro.core.ranking implementation."""
    from repro.core.ranking import RankWeights, maiz_ranking
    n = 2048
    ec = jnp.asarray(rng.random(n) * 10, jnp.float32)
    pue = jnp.asarray(1 + rng.random(n), jnp.float32)
    ci = jnp.asarray(rng.random(n) * 400, jnp.float32)
    fc = jnp.asarray(rng.random(n) * 400, jnp.float32)
    eff = jnp.asarray(rng.random(n), jnp.float32)
    sw = jnp.asarray(rng.random(n), jnp.float32)
    w = RankWeights()
    scores_mod = maiz_ranking(ec * pue * ci, ec * pue * fc, eff, sw, w)
    scores_k, _, _ = maiz_ranking_fused(
        ec, pue, ci, fc, eff, sw, w.as_array(), interpret=True)
    np.testing.assert_allclose(np.asarray(scores_k), np.asarray(scores_mod),
                               atol=1e-5)


def _rank_streams(rng, n):
    """Random f32 node streams for the ranking kernel, incl. the marginal
    ones: some nodes fully free (cap == chips_total) to hit the wake
    branch, some partially occupied."""
    ec = jnp.asarray(rng.random(n) * 100, jnp.float32)
    pue = jnp.asarray(1 + rng.random(n), jnp.float32)
    ci = jnp.asarray(rng.random(n) * 500, jnp.float32)
    fc = jnp.asarray(rng.random(n) * 500, jnp.float32)
    eff = jnp.asarray(rng.random(n), jnp.float32)
    sw = jnp.asarray(rng.random(n), jnp.float32)
    pk = jnp.asarray(rng.random(n) * 8, jnp.float32)
    ct = jnp.asarray(rng.choice([64.0, 128.0], n), jnp.float32)
    cap = jnp.where(jnp.asarray(rng.random(n)) < 0.3, ct,
                    jnp.floor(jnp.asarray(rng.random(n), jnp.float32) * ct))
    return ec, pue, ci, fc, eff, sw, pk, cap, ct


W4 = jnp.asarray([0.35, 0.25, 0.25, 0.15], jnp.float32)


@pytest.mark.parametrize("n", [1024, 5000, MULTI_BLOCK])
@pytest.mark.parametrize("idle", [0.2, 0.35])
@pytest.mark.parametrize("emb_h", [0.0, 120.0])
@pytest.mark.parametrize("w_m", [0.0, 0.3])
def test_maiz_ranking_kernel_marginal_matches_ref(n, idle, emb_h, w_m, rng):
    """The en_*-threaded generalized score (EnergyModel idle/dyn fractions,
    embodied wake price, marginal-CFP weight) matches the jnp oracle across
    the (idle x embodied x marginal) grid, argmin exact."""
    ec, pue, ci, fc, eff, sw, pk, cap, ct = _rank_streams(rng, n)
    en = jnp.asarray([idle, 1.0 - idle, emb_h, w_m], jnp.float32)
    mkw = dict(pk=pk, cap=cap, chips_total=ct, en=en)
    scores, top_s, top_i = maiz_ranking_topk(
        ec, pue, ci, fc, eff, sw, W4, k=8, interpret=True, **mkw)
    lohi = ref.term_lohi(ec, pue, ci, fc, eff, sw, **mkw)
    assert lohi.shape == (5, 2)
    want, want_min, want_arg = ref.maiz_ranking_ref(
        ec, pue, ci, fc, eff, sw, lohi, W4, **mkw)
    np.testing.assert_allclose(np.asarray(scores), np.asarray(want),
                               atol=1e-5)
    assert int(top_i[0]) == int(want_arg)


def test_maiz_ranking_kernel_marginal_weight_zero_is_bitwise_noop(rng):
    """en[3] == 0 makes the fifth term add ±0.0 — scores and shortlist are
    BITWISE the historical 4-term kernel's (the property the default-model
    golden digests lean on)."""
    ec, pue, ci, fc, eff, sw, pk, cap, ct = _rank_streams(rng, 2048)
    en0 = jnp.asarray([0.35, 0.65, 120.0, 0.0], jnp.float32)
    s4, t4, i4 = maiz_ranking_topk(ec, pue, ci, fc, eff, sw, W4, k=16,
                                   interpret=True)
    s5, t5, i5 = maiz_ranking_topk(ec, pue, ci, fc, eff, sw, W4, k=16,
                                   pk=pk, cap=cap, chips_total=ct, en=en0,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(s4).view(np.int32),
                                  np.asarray(s5).view(np.int32))
    np.testing.assert_array_equal(np.asarray(t4).view(np.int32),
                                  np.asarray(t5).view(np.int32))
    np.testing.assert_array_equal(np.asarray(i4), np.asarray(i5))


def _room_kw(marginal, masked, cap, room_min):
    """Room-threshold arguments: with the marginal streams the kernel
    reads ``cap`` as the room, else the room is a stream of its own."""
    if not masked:
        return {}
    return (dict(room_min=room_min) if marginal
            else dict(room=cap, room_min=room_min))


BATCHED_CASES = [(m, r, n) for n in (ONE_BLOCK, WIDE_BLOCK, MULTI_BLOCK)
                 for m, r in ((False, False), (True, False),
                              (False, True), (True, True))]


@pytest.mark.parametrize(
    "marginal,masked,n", BATCHED_CASES,
    ids=[f"{m}{'-room' if r else ''}{'' if n == ONE_BLOCK else f'-n{n}'}"
         for m, r, n in BATCHED_CASES])
def test_maiz_ranking_topk_batched_matches_sequential(marginal, masked, n,
                                                      rng):
    """Every lane of the ONE-launch (L x node-blocks) batched kernel is
    bitwise the sequential kernel on that lane — the property the
    ensemble driver's scan parity rests on; with a room threshold of
    each lane's own too; in one block and in several."""
    L = 3
    lanes = [_rank_streams(rng, n) for _ in range(L)]
    stack = [jnp.stack([lane[i] for lane in lanes]) for i in range(9)]
    ec, pue, ci, fc, eff, sw, pk, cap, ct = stack
    en = jnp.asarray([[0.35, 0.65, 50.0, 0.2],
                      [0.20, 0.80, 0.0, 0.4],
                      [0.30, 0.70, 120.0, 0.0]], jnp.float32)
    room_min = jnp.asarray([1, 40, 100], jnp.int32)
    mkw_b = dict(pk=pk, cap=cap, chips_total=ct, en=en) if marginal else {}
    sb, tb, ib = maiz_ranking_topk_batched(
        ec, pue, ci, fc, eff, sw, W4, k=16, interpret=True,
        **mkw_b, **_room_kw(marginal, masked, cap, room_min))
    for l in range(L):
        mkw = dict(pk=pk[l], cap=cap[l], chips_total=ct[l],
                   en=en[l]) if marginal else {}
        mkw.update(_room_kw(marginal, masked, cap[l], room_min[l]))
        s, t, i = maiz_ranking_topk(
            ec[l], pue[l], ci[l], fc[l], eff[l], sw[l], W4, k=16,
            interpret=True, **mkw)
        np.testing.assert_array_equal(np.asarray(sb[l]).view(np.int32),
                                      np.asarray(s).view(np.int32))
        np.testing.assert_array_equal(np.asarray(tb[l]).view(np.int32),
                                      np.asarray(t).view(np.int32))
        np.testing.assert_array_equal(np.asarray(ib[l]), np.asarray(i))


def _room_case(layout, n, rng):
    """Free chips (0-64) for a room-threshold case at ``room_min`` 32:
    about half the nodes qualify (``ragged``: n off the tile, so the
    padded tail is masked too), none of the first tile does
    (``whole_tile``), none of the first of several blocks does
    (``whole_block``), or only five do (``few``, fewer than k)."""
    room = rng.integers(0, 65, n).astype(np.float32)
    if layout == "whole_tile":
        room[:1024] = rng.integers(0, 32, 1024)
    elif layout == "whole_block":
        nb, bt = block_tiles(-(-n // 1024))
        assert nb > 1
        room[:bt * 1024] = rng.integers(0, 32, bt * 1024)
    elif layout == "few":
        room = rng.integers(0, 32, n).astype(np.float32)
        room[rng.choice(n, 5, replace=False)] = 48.0
    return jnp.asarray(room)


@pytest.mark.parametrize("marginal", [False, True])
@pytest.mark.parametrize("layout,n", [("ragged", 2050),
                                      ("whole_tile", 3000),
                                      ("few", 2500),
                                      ("ragged", WIDE_BLOCK),
                                      ("ragged", MULTI_BLOCK),
                                      ("whole_block", MULTI_BLOCK)])
def test_maiz_ranking_topk_room_mask_matches_lax_topk(layout, n, marginal,
                                                      rng):
    """Nodes below the room threshold score +inf in the scores and are
    ranked last: the top-k equals lax.top_k over the unmasked kernel's
    scores masked in jnp, ties included; past the nodes with room the
    top-k scores +inf.  The batched kernel agrees, a threshold per lane."""
    ec, pue, ci, fc, eff, sw, pk, _, ct = _rank_streams(rng, n)
    ct = jnp.full((n,), 64.0, jnp.float32)
    room = _room_case(layout, n, rng)
    en = jnp.asarray([0.35, 0.65, 50.0, 0.2], jnp.float32)
    mkw = dict(pk=pk, cap=room, chips_total=ct, en=en) if marginal else {}
    args = (ec, pue, ci, fc, eff, sw, W4)
    k = 16
    full, _, _ = maiz_ranking_topk(*args, k=k, interpret=True, **mkw)
    want = jnp.where(room >= 32, full, jnp.inf)
    neg, idx = jax.lax.top_k(-want, k)
    n_room = int(jnp.sum(room >= 32))
    head = min(n_room, k)

    def check(s, t, i):
        np.testing.assert_array_equal(np.asarray(s).view(np.int32),
                                      np.asarray(want).view(np.int32))
        np.testing.assert_array_equal(np.asarray(t)[:head],
                                      np.asarray(-neg)[:head])
        np.testing.assert_array_equal(np.asarray(i)[:head],
                                      np.asarray(idx)[:head])
        assert np.all(np.isinf(np.asarray(t)[head:]))

    check(*maiz_ranking_topk(*args, k=k, interpret=True, **mkw,
                             **_room_kw(marginal, True, room, 32)))
    assert (n_room < k) == (layout == "few")
    # lane 1 keeps every node: a threshold of 0 masks only the tail
    bkw = {key: jnp.stack([v, v]) for key, v in mkw.items()}
    sb, tb, ib = maiz_ranking_topk_batched(
        *(jnp.stack([a, a]) for a in args[:6]), W4, k=k, interpret=True,
        **bkw, **_room_kw(marginal, True, jnp.stack([room, room]),
                          jnp.asarray([32, 0], jnp.int32)))
    check(sb[0], tb[0], ib[0])
    np.testing.assert_array_equal(np.asarray(sb[1]).view(np.int32),
                                  np.asarray(full).view(np.int32))
    neg1, idx1 = jax.lax.top_k(-full, k)
    np.testing.assert_array_equal(np.asarray(ib[1]), np.asarray(idx1))


@pytest.mark.parametrize("n", [ONE_BLOCK, WIDE_BLOCK, MULTI_BLOCK])
def test_maiz_ranking_topk_ties_across_tiles_take_the_lower_node(n, rng):
    """Equal scores in different tiles of one block (and, past one block,
    in different blocks) come out lower node first: with more tied best
    nodes than k, the shortlist is the k lowest of them, bitwise
    lax.top_k over the kernel's scores; the batched kernel agrees."""
    ec, pue, ci, fc, eff, sw = (np.array(a) for a in _rank_streams(rng, n)[:6])
    # node 1023 (tile 0, last row and lane) before node 1024 (tile 1,
    # first row and lane): the tied nodes get every input's best value,
    # so they score alike and below every other node
    tied = np.unique(np.concatenate([[1023, 1024],
                                     rng.choice(n, 30, replace=False)]))
    for a, v in ((ec, 0.0), (pue, 1.0), (ci, 0.0), (fc, 0.0), (eff, 1.0),
                 (sw, 0.0)):
        a[tied] = v
    args = [jnp.asarray(a) for a in (ec, pue, ci, fc, eff, sw)]
    k = 16
    s, t, i = maiz_ranking_topk(*args, W4, k=k, interpret=True)
    st = np.asarray(s)
    assert np.all(st[tied] == st[tied[0]])
    assert st[tied[0]] < np.delete(st, tied).min()
    neg, idx = jax.lax.top_k(-s, k)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(t).view(np.int32),
                                  np.asarray(-neg).view(np.int32))
    np.testing.assert_array_equal(np.asarray(i), tied[:k])
    sb, tb, ib = maiz_ranking_topk_batched(
        *(jnp.stack([a, a]) for a in args), W4, k=k, interpret=True)
    for l in range(2):
        np.testing.assert_array_equal(np.asarray(ib[l]), tied[:k])
        np.testing.assert_array_equal(np.asarray(tb[l]).view(np.int32),
                                      np.asarray(t).view(np.int32))


def test_maiz_topk_tile_k_limit_is_actionable():
    """Asking the raw tile kernel for k > MAX_TILE_K names the limit and
    the knobs (the public wrappers fall back to a host-side merge
    instead — covered by test_placement's oversized-shortlist case)."""
    from repro.kernels.maizx_rank import MAX_TILE_K, maiz_topk_pallas
    n_valid = jnp.full((1, 1), 1024, jnp.int32)
    args = [jnp.ones(1024, jnp.float32)] * 6
    lohi = jnp.zeros((4, 2), jnp.float32)
    with pytest.raises(ValueError, match=r"MAX_TILE_K") as ei:
        maiz_topk_pallas(*args, n_valid, lohi, W4, k=MAX_TILE_K + 1,
                         interpret=True)
    assert "shortlist" in str(ei.value)   # tells the caller which knob


SCAN_CASES = [
    # (B, S, D, N, block_d, q_chunk, dtype)
    (2, 32, 128, 16, 128, 16, jnp.float32),
    (1, 64, 256, 16, 128, 32, jnp.float32),
    (2, 48, 128, 8, 64, 16, jnp.float32),
    (1, 32, 128, 16, 128, 8, jnp.bfloat16),
]


@pytest.mark.parametrize("case", SCAN_CASES,
                         ids=[f"B{c[0]}S{c[1]}D{c[2]}N{c[3]}bd{c[4]}q{c[5]}"
                              f"{c[6].__name__}" for c in SCAN_CASES])
def test_selective_scan_kernel_matches_ref(case, rng):
    B, S, D, N, bd, q, dt_ = case
    dt = jnp.asarray(rng.random((B, S, D)) * 0.1 + 0.01, dt_)
    x = jnp.asarray(rng.standard_normal((B, S, D)), dt_)
    b = jnp.asarray(rng.standard_normal((B, S, N)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((B, S, N)), jnp.float32)
    a = jnp.asarray(-np.exp(rng.standard_normal((D, N)) * 0.3), jnp.float32)
    got = selective_scan_op(dt, x, b, c, a, block_d=bd, q_chunk=q,
                            interpret=True)
    want = ref.selective_scan_ref(dt, x, b, c, a)
    tol = 2e-6 if dt_ == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_selective_scan_kernel_matches_module_scan(rng):
    """Kernel == the chunked_selective_scan module path (same recurrence)."""
    from repro.models.ssm import chunked_selective_scan
    B, S, D, N = 2, 40, 128, 16
    dt = jnp.asarray(rng.random((B, S, D)) * 0.1 + 0.01, jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, S, D)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((B, S, N)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((B, S, N)), jnp.float32)
    a = jnp.asarray(-np.exp(rng.standard_normal((D, N)) * 0.3), jnp.float32)
    dA = jnp.exp(dt[..., None] * a)
    dBx = (dt * x)[..., None] * b[:, :, None, :]
    h_all, _ = chunked_selective_scan(dA, dBx,
                                      jnp.zeros((B, D, N), jnp.float32),
                                      chunk=8)
    want = jnp.einsum("bsmn,bsn->bsm", h_all, c)
    got = selective_scan_op(dt, x, b, c, a, block_d=64, q_chunk=8,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
