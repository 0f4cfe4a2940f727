"""The block-diffusion MoE family (models/block_diffusion_moe.py) against its
plain reference (models/block_diffusion_moe_reference.py): three layers at
hidden 32, seeded random weights, on the CPU mesh — the noisy half's logits,
loss and every leaf's gradient through ``build_train_step`` with the batch's
third leaf; the one-pass form against the block-by-block definition; what a
noisy block's logits may and may not depend on; the eight shares of the
experts adding up to the uncut layer; what the step counts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.data import block_diffusion_noise
from byteps_tpu.models import block_diffusion_moe as bd
from byteps_tpu.models import block_diffusion_moe_reference as ref
from byteps_tpu.models import moe_family as mf
from byteps_tpu.models import transformer as tfm
from byteps_tpu.parallel import moe

import family_cases as fc
from family_cases import _mesh


def _state(cfg, seed=0, batch=4):
    """Parameters with the norms' scales off their starting value, the noised
    copy of clean tokens that are never the mask token, the clean tokens and
    the rows' weights."""
    params, _, _ = fc._state(bd, cfg, seed, batch)
    mask_id = cfg.vocab_size - 1
    clean = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, cfg.max_seq), 0, mask_id)
    noisy, weights = block_diffusion_noise(jax.random.PRNGKey(seed + 2), clean,
                                           cfg.block_length, mask_id, 0.3, 0.9)
    return params, noisy, clean, weights


FAMILY = fc.Family(
    name="block_diffusion_moe", model=bd, ref=ref, tiny=bd.tiny_block_diffusion_moe, state=_state,
    variants={
        "three_layers_blocks_of_four": dict(),
        "blocks_of_two": dict(block_length=2),
        "one_block_is_the_sequence": dict(block_length=16),
        "one_head_a_key_value_head": dict(n_kv_heads=4),
        "one_layer_is_a_last_layer": dict(n_layers=1),
        "held_share_of_experts": dict(experts_held=2, expert_lo=4),
        "no_remat": dict(remat=False),
    },
    learns=lambda cfg, name: True,  # every leaf learns
    dp2=("three_layers_blocks_of_four", 1e-5),
)
globals().update(fc.family_cases(FAMILY))


@pytest.mark.parametrize("variant", FAMILY.params_of(sorted(FAMILY.variants)))
def test_the_noisy_halfs_logits_match_reference(tiny, variant):
    t = tiny(variant)
    params, noisy, clean, _ = t.state
    got = jax.jit(lambda p, a, b: bd.local_logits(t.cfg, p, a, b))(params, noisy, clean)
    want = jax.jit(lambda p, a, b: ref.one_pass_logits(t.cfg, p, a, b))(params, noisy, clean)
    assert got.shape == noisy.shape + (t.cfg.vocab_size,)
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("variant", ["three_layers_blocks_of_four", "blocks_of_two",
                                     "one_block_is_the_sequence", "held_share_of_experts"])
def test_the_one_pass_form_is_the_block_by_block_definition(tiny, variant):
    """Block ``b``'s logits from the model run on ``[x_0^{<b}, x_t^b]`` under
    the block-causal mask are the one pass's — and so is the loss."""
    t = tiny(variant)
    params, noisy, clean, weights = t.state
    one = jax.jit(lambda *a: ref.one_pass_logits(t.cfg, *a))(params, noisy, clean)
    by_block, loss = jax.jit(lambda p, a, b, w: (  # one program for the blocks' runs
        ref.block_by_block_logits(t.cfg, p, a, b),
        ref.loss(t.cfg, p, a, b, w, ref.block_by_block_logits)))(params, noisy, clean, weights)
    np.testing.assert_allclose(one, by_block, atol=2e-5 * float(jnp.abs(one).max()))
    assert float(t.reference()[0]) == pytest.approx(float(loss), rel=1e-5)


def _moved(cfg, params, noisy, clean, noisy2, clean2):
    """Per block, whether the program's logits moved between two inputs."""
    logits = jax.jit(lambda a, b: bd.local_logits(cfg, params, a, b))
    was = logits(noisy, clean)
    apart = jnp.abs(was - logits(noisy2, clean2)).max(axis=-1)  # (B, L)
    # a row's sums may come in another order when other rows' slots move among
    # the experts: 1e-6 of a logit; a key that is read moves it by a tenth
    by_block = apart.reshape(apart.shape[0], -1, cfg.block_length).max(axis=(0, 2))
    return np.asarray(by_block > 1e-4 * float(jnp.abs(was).max()))


#: what is changed → (which copy, which block), and which blocks' logits may move
LEAKS = {
    "another_blocks_noisy_tokens": ("noisy", 1, [False, True, False, False]),
    "its_own_clean_tokens": ("clean", 2, [False, False, False, True]),
    "a_later_blocks_clean_tokens": ("clean", 3, [False, False, False, False]),
    "an_earlier_clean_token": ("clean", 0, [False, True, True, True]),
}


@pytest.mark.parametrize("case", sorted(LEAKS))
def test_a_noisy_blocks_logits_read_what_the_mask_says_and_nothing_else(tiny, case):
    """No leak: block ``b``'s logits do not move when any other block's
    ``x_t`` changes, nor when the clean tokens of its own or a later block do;
    they do move when an earlier clean token does (and a block's own noisy
    tokens move its own logits alone)."""
    t = tiny("three_layers_blocks_of_four")
    params, noisy, clean, _ = t.state
    copy, block, want = LEAKS[case]
    at = slice(block * t.cfg.block_length, (block + 1) * t.cfg.block_length)
    changed = {"noisy": noisy, "clean": clean}
    changed[copy] = changed[copy].at[:, at].set((changed[copy][:, at] + 7) % (t.cfg.vocab_size - 1))
    moved = _moved(t.cfg, params, noisy, clean, changed["noisy"], changed["clean"])
    assert moved.tolist() == want


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The held experts' parts over all the shares of a deployment sum to the
    uncut reference's layer: what a share leaves out is what the others add."""
    whole = bd.tiny_block_diffusion_moe()  # 8 experts, all held
    lp = mf.stack_of(bd.init_params(whole, jax.random.PRNGKey(4)), "moe")
    lp = {k: v[0] for k, v in lp.items()}
    g = jax.random.normal(jax.random.PRNGKey(5), (24, whole.d_model))
    with jax.default_matmul_precision("highest"):
        want = ref.moe_mlp(whole, g, lp)
        total, slots = 0.0, 0
        for share in range(4):  # four shares of two experts
            cfg = bd.tiny_block_diffusion_moe(experts_held=2, expert_lo=2 * share)
            held = {**lp, **{k: lp[k][2 * share:2 * share + 2]
                             for k in ("e_gate", "e_up", "e_down")}}
            y, stats = bd.moe_mlp(cfg, g, held)
            np.testing.assert_allclose(y, ref.moe_mlp(cfg, g, held), atol=2e-5)
            total, slots = total + y, slots + int(stats[1])
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert slots == 24 * whole.top_k  # every slot is held by exactly one share


def test_the_step_takes_the_batchs_third_leaf_and_counts_what_it_saw():
    """``weights`` goes in after ``targets``; masked rows and the mean weight
    reach the process's counters beside the routing statistics."""
    cfg = bd.tiny_block_diffusion_moe()
    params, noisy, clean, weights = _state(cfg, batch=2)
    tx = optax.sgd(0.0)
    step = tfm.build_train_step(cfg, _mesh(), tx, donate=False)
    sink = moe.routing_counters()
    before = sink._snapshot()
    for _ in range(2):
        loss = step(params, tx.init(params), noisy, clean, weights)[2]
    after = sink._snapshot()
    grown = {k: after[k] - before.get(k, 0) for k in bd.COUNTS + moe.ROUTING_STATS}
    assert grown["block_diffusion_masked_tokens"] == 2 * int(jnp.sum(weights > 0))
    assert grown["block_diffusion_weight_milli"] == 2 * int(jnp.round(1000 * jnp.mean(weights)))
    # two layers over both copies and the last over the noisy half: 5 x L rows a sequence
    assert grown["moe_slots_routed"] == grown["moe_slots_held"] == 2 * 2 * 5 * 16 * cfg.top_k
    assert grown["moe_slots_dropped"] == 0
    assert float(loss) == pytest.approx(
        float(jax.jit(lambda *a: ref.loss(cfg, *a))(params, noisy, clean, weights)), rel=1e-5)
    with pytest.raises(TypeError, match=r"\('tokens', 'targets', 'weights'\): 2 leaves given"):
        step(params, tx.init(params), noisy, clean)


def test_unit_weights_on_every_row_are_the_mean_cross_entropy():
    """At weight 1 a row the loss is ``xent_sums``' mean over the noisy half's
    rows: the weighted blocked loss is the unweighted one's own implementation."""
    cfg = bd.tiny_block_diffusion_moe()
    params, noisy, clean, _ = _state(cfg, batch=2)
    tx = optax.sgd(0.0)
    loss = tfm.build_train_step(cfg, _mesh(), tx, donate=False)(
        params, tx.init(params), noisy, clean, jnp.ones(noisy.shape, jnp.float32))[2]
    logits = jax.jit(lambda *a: ref.one_pass_logits(cfg, *a))(params, noisy, clean)
    gold = jnp.take_along_axis(logits, clean[..., None], axis=-1)[..., 0]
    assert float(loss) == pytest.approx(
        float(jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)), rel=1e-5)


def test_a_family_that_declares_no_leaf_takes_two():
    """The step of a family without ``batch_leaves`` is the step it was."""
    from byteps_tpu.models import looped_dense as ld

    cfg = ld.tiny_looped_dense(n_layers=1, n_loops=1)
    assert not hasattr(cfg, "batch_leaves")
    params = ld.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, cfg.max_seq), 0, cfg.vocab_size)
    tx = optax.sgd(0.0)
    step = tfm.build_train_step(cfg, _mesh(), tx, donate=False)
    with pytest.raises(TypeError, match=r"\('tokens', 'targets'\): 3 leaves given"):
        step(params, tx.init(params), tokens, tokens, jnp.ones(tokens.shape))
    assert np.isfinite(float(step(params, tx.init(params), tokens, tokens)[2]))


def test_a_mesh_beyond_data_parallel_is_refused_in_the_familys_words():
    from byteps_tpu.parallel.mesh_utils import make_training_mesh

    cfg = bd.tiny_block_diffusion_moe()
    mesh = make_training_mesh(2, {"dp": 1, "pp": 1, "sp": 2, "tp": 1}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="the block-diffusion MoE family runs data-parallel "
                                         "only.*sp=2.*block mask across sequence shards"):
        tfm.build_train_step(cfg, mesh, optax.sgd(0.0))
    with pytest.raises(ValueError, match="blocks of 3 do not tile"):
        bd.tiny_block_diffusion_moe(block_length=3)
    with pytest.raises(ValueError, match="multiple of key/value heads"):
        bd.tiny_block_diffusion_moe(n_kv_heads=3)
    with pytest.raises(ValueError, match="lie outside the router's"):
        bd.tiny_block_diffusion_moe(experts_held=4, expert_lo=6)
    with pytest.raises(NotImplementedError, match="read both copies"):
        tfm.build_forward(cfg, _mesh())(bd.init_params(cfg, jax.random.PRNGKey(0)),
                                        jnp.zeros((1, 16), jnp.int32))
