"""The pieces the block-diffusion family brought: the mask and its table of
tiles (``ops/flash_attention.py``), the kernels in the Pallas interpreter
against dense attention — forward, ``dq | dk | dv`` and through ``lse`` — at
block lengths 4, 8 and 32 and at tiles that do and do not align with the two
halves; the noising's law (``data.block_diffusion_noise``); each copy's
positions; what the last layer leaves out.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.data import block_diffusion_noise
from byteps_tpu.models import block_diffusion_moe as bd
from byteps_tpu.models import block_diffusion_moe_reference as ref
from byteps_tpu.models import moe_family as mf

fa = importlib.import_module("byteps_tpu.ops.flash_attention")


def _clauses(rows, half, block):
    """The three clauses as the issue writes them, entry by entry (numpy)."""
    r, c = np.arange(rows)[:, None], np.arange(2 * half)[None, :]
    ni, nj = r < half, c < half
    bi, bj = (r % half) // block, (c % half) // block
    return (ni & nj & (bi == bj)) | (ni & ~nj & (bi > bj)) | (~ni & ~nj & (bi >= bj))


@pytest.mark.parametrize("half, block", [(32, 4), (48, 8), (64, 32), (24, 3), (16, 16)])
def test_the_mask_is_the_three_clauses(half, block):
    for rows in (half, 2 * half):
        want = _clauses(rows, half, block)
        got = fa.block_diffusion_visible(np.arange(rows)[:, None], np.arange(2 * half)[None, :],
                                         half, block)
        assert (got == want).all()
        traced = jax.jit(lambda r, c: fa.block_diffusion_visible(r, c, half, block))(
            jnp.arange(rows)[:, None], jnp.arange(2 * half)[None, :])
        assert (np.asarray(traced) == want).all()
    # L^2 + L B entries over 2L rows: half of a causal mask's 2L (2L + 1) / 2, about
    assert want.sum() == half * half + half * block
    assert want[:half].sum() == want[half:].sum()  # either half's queries see as many
    assert (np.asarray(ref.visible(half, block)) == want).all()  # the reference's own


@pytest.mark.parametrize("half, block, bq, bk", [
    (32, 4, 8, 8), (32, 4, 16, 8), (32, 4, 8, 16), (32, 4, 32, 32), (48, 8, 32, 32),
    (48, 4, 16, 32), (64, 32, 64, 64), (24, 3, 12, 8)])
def test_the_table_lists_the_tiles_that_hold_a_visible_entry(half, block, bq, bk):
    """Tile by tile against the mask itself: which pairs are listed (in
    ascending order, by query tile and by key tile), which are visible whole,
    and that the rest of a row repeats its last tile."""
    for rows in (half, 2 * half):
        if rows % bq:
            continue
        table = fa._bd_tiles(rows, half, block, bq, bk)
        nq, nk = rows // bq, 2 * half // bk
        tiles = _clauses(rows, half, block).reshape(nq, bq, nk, bk)
        need, whole = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
        assert table["pairs"] == need.sum()
        for of, n, full, by_row, by_whole in (
                ("kv_of", "n_kv", "kv_whole", need, whole), ("q_of", "n_q", "q_whole", need.T, whole.T)):
            listed = table[of].reshape(by_row.shape[0], -1)
            for i, row in enumerate(by_row):
                at = np.flatnonzero(row)
                assert table[n][i] == len(at) and listed[i, :len(at)].tolist() == at.tolist()
                assert (listed[i, len(at):] == at[-1]).all()
                assert (table[full].reshape(listed.shape)[i, :len(at)] == by_whole[i, at]).all()
        assert table["first_kv"].tolist() == [int(np.flatnonzero(r)[0]) for r in need]
        # the clean-query x noisy-key quadrant is empty, noisy x noisy its diagonal alone
        if half % bq == 0 and half % bk == 0 and rows == 2 * half:
            assert not need[half // bq:, :half // bk].any()
            if bq == bk:
                assert need[:half // bq, :half // bk].sum() == half // bq


def test_tiles_that_leave_a_tile_alone_are_refused():
    with pytest.raises(ValueError, match="some tile meets no other"):
        fa._bd_tiles(48, 48, 8, 8, 8)  # the noisy queries alone: nobody sees the last clean block
    with pytest.raises(ValueError, match="two copies of L tokens"):
        fa.block_diffusion_attention(jnp.zeros((1, 2, 24, 8)), jnp.zeros((1, 2, 32, 8)),
                                     jnp.zeros((1, 2, 32, 8)), 4)
    with pytest.raises(ValueError, match="block length 5 divides L"):
        fa.block_diffusion_attention(jnp.zeros((1, 2, 32, 8)), jnp.zeros((1, 2, 32, 8)),
                                     jnp.zeros((1, 2, 32, 8)), 5)


#: (L, block length, query rows, tile rows of q, of k): tiles that align with
#: the halves and tiles that straddle them, both copies' queries and the noisy
#: half's alone, every block length the issue names
KERNEL_CASES = {
    "b4_aligned": (32, 4, 64, 16, 16),
    "b4_noisy_queries_alone": (32, 4, 32, 16, 16),
    "b4_straddling_the_halves": (48, 4, 96, 32, 32),
    "b8_unequal_tiles": (32, 8, 64, 8, 16),
    "b8_noisy_alone_unequal": (48, 8, 48, 16, 32),
    "b32_a_block_a_tile": (64, 32, 128, 32, 64),
    "b32_straddling": (96, 32, 192, 64, 64),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_kernels_are_dense_attention_under_the_mask(case):
    """``flash_fwd_bd`` | ``flash_bwd_bd`` in the interpreter: the output, the
    logsumexp, and dq | dk | dv of a loss that reads both — grouped heads."""
    half, block, rows, bq, bk = KERNEL_CASES[case]
    h, h_kv, d = 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(rows + block), 5)
    q = jax.random.normal(ks[0], (1, h, rows, d))
    k, v = (jax.random.normal(key, (1, h_kv, 2 * half, d)) for key in ks[1:3])
    w_out, w_lse = jax.random.normal(ks[3], (1, h, rows, d)), jax.random.normal(ks[4], (1, h, rows))

    def both(fn):
        def loss(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(out * w_out) + jnp.sum(lse * w_lse), (out, lse)
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    (_, got), got_grads = both(lambda q, k, v: fa.block_diffusion_attention_lse(
        q, k, v, block, block_q=bq, block_k=bk, interpret=True))
    (_, want), want_grads = both(
        lambda q, k, v: fa._dense_block_diffusion_lse(q, k, v, block, d ** -0.5))
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), (*got, *got_grads),
                          (*want, *want_grads)):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()), err_msg=name)
    # and the dense form is attention under the clauses, by hand
    seen = _clauses(rows, half, block)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, axis=1)) * d ** -0.5
    by_hand = jnp.einsum("bhqk,bhkd->bhqd",
                         jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1),
                         jnp.repeat(v, 2, axis=1))
    np.testing.assert_allclose(want[0], by_hand, atol=2e-6)


def test_the_kernels_carry_their_own_names():
    assert (fa.FWD_BD_KERNEL, fa.BWD_BD_KERNEL) == ("flash_fwd_bd", "flash_bwd_bd")
    q = jnp.zeros((1, 2, 32, 8))
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(fa.block_diffusion_attention(
        q, q, q, 4, block_q=16, block_k=16, interpret=True))))(q))
    assert text.count("pallas_call") == 2  # one forward kernel, ONE backward kernel
    assert "flash_fwd_bd" in text and "flash_bwd_bd" in text
    assert "flash_fwd_win" not in text and "flash_bwd_win" not in text


def test_the_tuned_blocks_of_a_call_divide_it(monkeypatch):
    monkeypatch.setitem(fa._tuned_table()["block_diffusion"], (16384, 4), (1024, 512))
    assert fa.tuned_block_diffusion_blocks(16384, 4, 16384) == (1024, 512)
    assert fa.tuned_block_diffusion_blocks(16384, 4, 8192) == (1024, 512)
    # no entry at another block length: the half's plain entry, which divides both
    bq, bk = fa.tuned_block_diffusion_blocks(16384, 8, 16384)
    assert (bq, bk) == fa.tuned_blocks(8192) and 8192 % bq == 0 and 8192 % bk == 0


# ---- the noising ------------------------------------------------------------------


@pytest.mark.parametrize("lo, hi", [(0.45, 0.95), (0.1, 1.0), (0.5, 0.5)])
def test_the_noisings_law(lo, hi):
    """A level a (sequence, block) in [lo, hi]; within a block the masked share
    is about the level; weights ``1 / t`` at masked rows and 0 elsewhere; the
    mean weight is 1 in expectation; the mask token is never a target."""
    block, mask_id = 64, 999
    clean = jax.random.randint(jax.random.PRNGKey(0), (8, 4096), 0, mask_id)
    noisy, w = jax.jit(lambda k, x: block_diffusion_noise(k, x, block, mask_id, lo, hi))(
        jax.random.PRNGKey(1), clean)
    noisy, w, clean = (np.asarray(x) for x in (noisy, w, clean))
    masked = noisy == mask_id
    assert (noisy[~masked] == clean[~masked]).all() and not (clean == mask_id).any()
    assert ((w > 0) == masked).all() and w.dtype == np.float32
    by_block = w.reshape(8, -1, block)
    level = 1.0 / by_block.max(axis=-1)  # a block of 64 at t >= 0.1 has a masked row
    assert ((by_block == 0) | np.isclose(by_block, 1.0 / level[..., None])).all()
    assert level.min() >= lo - 1e-6 and level.max() <= hi + 1e-6
    share = masked.reshape(8, -1, block).mean(axis=-1)
    assert np.abs(share - level).mean() < 0.06  # a binomial of 64 at t: s.d. <= 0.0625
    assert abs(share.mean() - (lo + hi) / 2) < 0.02
    assert abs(w.mean() - 1.0) < 0.03
    if lo < hi:
        assert level.std() > 0.05  # a draw a block, not one a batch
    with pytest.raises(ValueError, match="outside"):
        block_diffusion_noise(jax.random.PRNGKey(0), clean, block, mask_id, 0.0, 1.0)
    with pytest.raises(ValueError, match="do not tile"):
        block_diffusion_noise(jax.random.PRNGKey(0), clean[:, :100], block, mask_id, lo, hi)


def test_another_key_is_another_noise_and_the_same_key_the_same():
    clean = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 50)
    a, wa = block_diffusion_noise(jax.random.PRNGKey(1), clean, 4, 50, 0.45, 0.95)
    b, wb = block_diffusion_noise(jax.random.PRNGKey(1), clean, 4, 50, 0.45, 0.95)
    c, _ = block_diffusion_noise(jax.random.PRNGKey(2), clean, 4, 50, 0.45, 0.95)
    assert (a == b).all() and (wa == wb).all() and not (a == c).all()


# ---- positions, and what the last layer leaves out ---------------------------------


def test_each_copy_counts_its_own_positions():
    """A token's logits do not depend on which copy's row ``i`` it is at other
    than through the mask: with one block over the whole sequence and the
    noisy copy equal to the clean one, position ``i`` of either copy sits at
    rope position ``i`` — so the program agrees with a reference that turns
    rows by ``i mod L``, and disagrees with one that counts ``0 … 2L − 1``."""
    cfg = bd.tiny_block_diffusion_moe()
    params = bd.init_params(cfg, jax.random.PRNGKey(0))
    clean = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 95)
    noisy = clean.at[:, ::3].set(95)
    got = jax.jit(lambda *a: bd.local_logits(cfg, *a))(params, noisy, clean)
    rows = jnp.concatenate([noisy, clean], axis=1)
    seen = ref.visible(16, cfg.block_length)
    turned = jax.jit(lambda positions: ref.forward(cfg, params, rows, seen, positions)[:, :16])
    right, wrong = turned(jnp.concatenate([jnp.arange(16)] * 2)), turned(jnp.arange(32))
    scale = float(jnp.abs(right).max())
    np.testing.assert_allclose(got, right, atol=1e-4 * scale)
    assert float(jnp.abs(got - wrong).max()) > 1e-2 * scale


def test_the_last_layer_runs_the_noisy_half_alone():
    """Its routed slots are L rows', not 2L's, and the loss's gradient reaches
    the last layer's key and value projections through the clean half too."""
    cfg = bd.tiny_block_diffusion_moe(n_layers=2)
    params = bd.init_params(cfg, jax.random.PRNGKey(0))
    clean = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 95)
    noisy = clean.at[:, 1::2].set(95)
    x, stats = jax.jit(lambda *a: bd._hidden(cfg, *a))(params, noisy, clean)
    assert x.shape == (1, 16, cfg.d_model)
    assert int(stats[0]) == (32 + 16) * cfg.top_k  # routed: one full layer, one half

    def through_clean_keys(wk):
        logits = bd.local_logits(cfg, {**params, "attn.wk": wk}, noisy, clean)
        return jnp.sum(logits[:, 8:] ** 2)  # later blocks read earlier clean keys

    grad = jax.jit(jax.grad(through_clean_keys))(params["attn.wk"])
    assert float(jnp.abs(grad[-1]).max()) > 0  # the last layer's k: read, so it learns
    assert set(mf.stack_of(params, "attn")) == {"norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo"}
    assert set(mf.stack_of(params, "moe")) == {"norm", "router", "e_gate", "e_up", "e_down"}


def _masked_rows_fullest_share(cfg, params, seed=3):
    """Layer by layer, of the masked rows' routed slots the share that goes to
    the expert they choose most (1 / n_experts · … when even; 1 / top_k when
    every masked row chooses the same ``top_k``)."""
    from byteps_tpu.parallel.moe import softmax_topk_route

    mask_id = cfg.vocab_size - 1
    clean = jax.random.randint(jax.random.PRNGKey(seed), (1, cfg.max_seq), 0, mask_id)
    noisy, _ = block_diffusion_noise(jax.random.PRNGKey(seed + 1), clean, cfg.block_length,
                                     mask_id, 0.45, 0.95)
    rows = jnp.concatenate([noisy, clean], axis=1)
    masked = np.asarray(rows[0] == mask_id)

    @jax.jit
    def choices(params):
        """Every layer's chosen experts, row by row."""
        x = params["embed"][rows]
        attn, moe = mf.stack_of(params, "attn"), mf.stack_of(params, "moe")
        ids = []
        for i in range(cfg.n_layers):
            x = bd._attention_part(cfg, x, {k: v[i] for k, v in attn.items()})
            lp = {k: v[i] for k, v in moe.items()}
            ids.append(softmax_topk_route(mf.rms(x[0], lp["norm"], cfg.norm_eps), lp["router"],
                                          cfg.top_k)[0])
            x, _ = bd._moe_part(cfg, x, lp)
        return ids

    chosen = [np.bincount(np.asarray(ids)[masked].reshape(-1), minlength=cfg.n_experts)
              for ids in choices(params)]
    return [c.max() / c.sum() for c in chosen]


@pytest.mark.parametrize("start", ["unit_scales_collapse", "the_familys_start_spreads"])
def test_the_seeded_start_keeps_masked_rows_apart(start):
    """A third of the rows are ONE token.  Under unit q/k norm scales and a
    mask row like any other, seeded attention is a mean over the visible keys
    and every masked row carries one vector: all of them choose the same
    ``top_k`` experts in every layer (PERF.md §6 PR 59).  The family's start
    — the mask token's row small, q/k norm scales above one — makes a masked
    row what it attends to, and the rows differ."""
    cfg = bd.BlockDiffusionMoEConfig(vocab_size=512, d_model=256, n_layers=2, n_heads=4,
                                     n_kv_heads=2, head_dim=64, d_expert=32, n_experts=32,
                                     experts_held=4, top_k=4, max_seq=512)
    params = bd.init_params(cfg, jax.random.PRNGKey(11))
    if start == "unit_scales_collapse":
        fresh = mf.normal(1.0)(lambda: jax.random.PRNGKey(5), (cfg.d_model,))
        params = {**params, "embed": params["embed"].at[-1].set(fresh),
                  "attn.q_norm": jnp.ones_like(params["attn.q_norm"]),
                  "attn.k_norm": jnp.ones_like(params["attn.k_norm"])}
        assert min(_masked_rows_fullest_share(cfg, params)) > 0.9 / cfg.top_k
    else:
        assert float(params["attn.q_norm"][0, 0]) == bd.QK_START > 1.0
        assert float(jnp.abs(params["embed"][-1]).max()) < 5 * bd.MASK_ROW < 0.1
        assert max(_masked_rows_fullest_share(cfg, params)) < 0.6 / cfg.top_k
