"""The latent-attention MoE cell's yardstick pieces on the CPU: the benchmark's
blocked copy of the reference, its precision controls, and the readings that
leg E of chip_smoke.py and tools/latent_moe_precision.py take of a gradient.
(The model against its reference: tests/test_latent_moe.py; the layer and
the kernel: tests/test_latent_moe_pieces.py.)

Each program here costs 10–20 s to differentiate at the rehearsal cuts, so
every loss and gradient is made once a module and shared by the cases.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import latent_moe as lm
from byteps_tpu.models import latent_moe_reference as ref
from byteps_tpu.parallel import moe

import family_cases as fc
from family_cases import ROOT, _load, _worst

_state = functools.partial(fc._state, lm, bias=0.3)


@pytest.fixture(scope="module")
def rehearsal():
    """(the builder, the cell's configuration at its rehearsal cuts, the model
    config, state with a non-zero selection bias)."""
    builder = _load("benchmark/builders/joyai_llm_flash.py", "test_joyai_builder")
    with open(os.path.join(ROOT, "benchmark/configs/joyai_llm_flash_ep32.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearsal"])
    mcfg = builder._model_config(cfg)
    params, tokens, targets = _state(mcfg, batch=2)
    return builder, cfg, mcfg, params, (tokens, targets)


@pytest.fixture(scope="module")
def smoke():
    return _load("chip_smoke.py", "test_chip_smoke_module")


@pytest.fixture(scope="module")
def plain(rehearsal):
    """``plain(*precision)`` → (loss, gradients) of the builder's plain loss at
    that precision (none: f32 throughout), differentiated once."""
    builder, cfg, _, params, batch = rehearsal
    made = {}

    def at(*precision):
        if precision not in made:
            made[precision] = jax.jit(jax.value_and_grad(
                builder.plain_loss(cfg, *precision)))(params, batch)
        return made[precision]

    return at


def test_the_builders_blocked_copy_is_the_reference(rehearsal, plain):
    _, _, mcfg, params, batch = rehearsal
    got, grads = plain()
    want, want_grads = jax.jit(jax.value_and_grad(lambda p: ref.loss(mcfg, p, *batch)))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert _worst(grads, want_grads)[0] < 1e-4


@pytest.mark.parametrize("statistics, least, most", [
    (jnp.float32, 1e-7, 2e-3),   # the precision the configuration states
    (jnp.bfloat16, 1e-7, 2e-3),  # the nearest below: still a sound loss at these widths
])
def test_precision_controls_keep_f32_parameters_and_loss(plain, statistics, least, most):
    want = float(plain()[0])
    loss, grads = plain(jnp.bfloat16, statistics)
    assert loss.dtype == jnp.float32 and {g.dtype for g in grads.values()} == {jnp.dtype("float32")}
    assert least < abs(float(loss) - want) / want < most  # rounded somewhere, and not lost


def test_bf16_statistics_move_the_routers_gradient_most(plain):
    want = plain()[1]
    apart = {}
    for name, statistics in (("stated", jnp.float32), ("below", jnp.bfloat16)):
        got = plain(jnp.bfloat16, statistics)[1]
        apart[name] = max(float(jnp.linalg.norm(got[k] - want[k]) / jnp.linalg.norm(want[k]))
                          for k in want if k.endswith(".router"))
    assert apart["below"] > 1.5 * apart["stated"]


@pytest.mark.parametrize("fault, reading, least, most", [
    ("none", "projection", 0.0, 1e-6),
    ("halved_expert", "projection", 0.49, 0.51),
    ("lost_dense_leaf", "projection", 0.99, 1.01),
    ("halved_expert", "routed", 0.49, 0.51),
    ("lost_dense_leaf", "rest", 0.99, 1.01),
])
def test_gradient_readings_see_a_planted_fault(smoke, plain, fault, reading, least, most):
    want = plain()[1]
    got = dict(want)
    if fault == "halved_expert":
        got["moe.e_up"] = 0.5 * want["moe.e_up"]
    if fault == "lost_dense_leaf":
        got["dense.wo"] = jnp.zeros_like(want["dense.wo"])
    read = smoke.gradient_readings(got, jax.device_get(want))
    assert least <= read[reading][1] <= most
    assert read["zero"] == ["moe.router_bias", "mtp.router_bias"]
    if fault != "none":
        assert read[reading][0] == ("moe.e_up" if fault == "halved_expert" else "dense.wo")


def test_pinned_choice_sends_every_token_to_the_same_experts(smoke, rehearsal):
    _, cfg, mcfg, params, _ = rehearsal
    pinned = smoke.pin_choice(params, cfg)
    assert {k for k in params if pinned[k] is not params[k]} == {"moe.router_bias", "mtp.router_bias"}
    lp = {k.split(".", 1)[1]: v[0] for k, v in pinned.items() if k.startswith("moe.")}
    g = jax.random.normal(jax.random.PRNGKey(3), (40, mcfg.d_model))
    ids, _ = moe.sigmoid_topk_route(g, lp["router"], lp["router_bias"], mcfg.top_k, mcfg.routed_scale)
    assert {tuple(sorted(row)) for row in np.asarray(ids).tolist()} == {(0, 1, 2, 3, 8, 9, 10, 11)}
    _, stats = lm.expert_mlp(mcfg, g, lp)
    routed, held, dropped, fullest, walked = (int(v) for v in stats)
    # 16 x a uniform router's share: the first chunk and the tail chunks it takes, to the row
    assert (routed, held, dropped, fullest, walked) == (40 * 8, 40 * 4, 0, 40, 40 * 4)
