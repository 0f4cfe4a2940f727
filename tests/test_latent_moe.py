"""The latent-attention MoE family (models/latent_moe.py) against its plain
reference (models/latent_moe_reference.py): tiny widths, seeded random
weights, f32, on the CPU mesh.  (The pieces the family brought — the
held-expert layer, the flash kernel at d_qk != d_v, the routing counters:
tests/test_latent_moe_pieces.py.  The cell's blocked reference, its
precision controls and leg E's readings: tests/test_latent_moe_readings.py.
Three files so that `--dist loadfile` spreads them.)
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import latent_moe as lm
from byteps_tpu.models import latent_moe_reference as ref
from byteps_tpu.models import transformer as tfm

import family_cases as fc

_state = functools.partial(fc._state, lm, bias=0.3)  # a NON-ZERO selection bias

FAMILY = fc.Family(
    name="latent_moe", model=lm, ref=ref, tiny=lm.tiny_latent_moe, state=_state,
    variants={
        "dense_layer": dict(n_expert_layers=0, mtp_modules=0),
        "expert_layer_biased_choice": dict(n_dense_layers=0, n_expert_layers=1, mtp_modules=0),
        "two_kinds_no_mtp": dict(mtp_modules=0),
        "mtp_and_both_losses": dict(),
        "held_share_of_experts": dict(experts_held=2, expert_lo=4),
    },
    ref_logits=lambda cfg, p, x: ref.forward(cfg, p, x)[0],
    # one softmax and no scan between the products: tighter than the others'
    logits_atol=2e-5, grad_tol=1e-4,
    learns=lambda cfg, name: False if name.endswith("router_bias") else None,  # it picks
    dp2=("mtp_and_both_losses", 1e-5),
    refused_axes=dict.fromkeys(("pp", "sp", "tp"), "data-parallel only"),
)
globals().update(fc.family_cases(FAMILY))


def test_mtp_loss_is_a_second_term_with_its_weight(tiny):
    t = tiny("mtp_and_both_losses")
    with_mtp = t.reference()[0]
    main, double = jax.jit(lambda p, x, y: (
        ref.loss(lm.tiny_latent_moe(mtp_modules=0),
                 {k: v for k, v in p.items() if "mtp" not in k}, x, y),
        ref.loss(lm.tiny_latent_moe(mtp_lambda=0.6), p, x, y)))(t.params, t.tokens, t.targets)
    assert float(with_mtp) > float(main)
    assert float(double - main) == pytest.approx(2 * float(with_mtp - main), rel=1e-5)


# ---------------------------------------------------------------------------
# what shares build_train_step with this family stays as it was
# ---------------------------------------------------------------------------


def test_bert_tiny_preset_loss_is_bit_equal_to_before_this_family():
    """Recorded at the parent commit (f12c015) with this very script: two
    adamw steps of ``tiny_test()`` on one device."""
    cfg = tfm.tiny_test()
    mesh = fc._mesh()
    params = tfm.shard_params(tfm.init_params(cfg, seed=0), cfg, mesh)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, cfg.max_seq)), jnp.int32)
    tx = optax.adamw(1e-3)
    step = tfm.build_train_step(cfg, mesh, tx, donate=False)
    state, losses = tx.init(params), []
    for _ in range(2):
        params, state, loss = step(params, state, tokens, jnp.roll(tokens, -1, 1))
        losses.append(np.float32(loss).tobytes().hex())
    assert losses == ["b3eb9140", "c0a78b40"]
