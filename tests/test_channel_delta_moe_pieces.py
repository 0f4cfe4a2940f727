"""The channel-delta MoE cell's blocked reference
(benchmark/builders/kimi_linear.py) against the plain reference
(models/channel_delta_moe_reference.py) at toy widths, on the CPU: the blocking
is what is under test — the rule in runs of tokens and groups of heads, the
latent layer in groups of heads and blocks of queries, the MLPs and the logits
in blocks of rows.  And the family with the rule's Pallas kernels
(ops/kda_kernels.py) in the interpreter at a tiny shape that tiles: what a
rebuilt delta layer calls, and the step against the plain reference.  (The
family itself: tests/test_channel_delta_moe.py.  Two files so that ``--dist
loadfile`` spreads them.)
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

from byteps_tpu.models import channel_delta_moe as cd
from byteps_tpu.models import channel_delta_moe_reference as ref
from byteps_tpu.ops import gated_delta as gd
from byteps_tpu.ops import kda_kernels as kk

import family_cases as fc

_state = functools.partial(
    fc._state, cd, moved=lambda name: "norm" in name or name.endswith("router_bias"))

# toy widths: the cell's first four layers (dense delta, two expert delta,
# expert latent: every kind once), four heads in four groups, two runs of tokens
globals().update(fc.builder_cases(
    "channel_delta_moe", ref, _state, builder="kimi_linear", config="kimi_linear_48b_ep32",
    toy=dict(num_hidden_layers=4, hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
             linear_attn_config={"full_attn_layers": [4], "kda_layers": [1, 2, 3, 5],
                                 "head_dim": 8, "num_heads": 4, "short_conv_kernel_size": 4},
             num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
             v_head_dim=8, num_experts=4, router_width=16, num_experts_per_token=3,
             vocab_size=96, max_seq=32, chunk=8),
    blocks=dict(RUN=16), never_learns=("moe.router_bias",),  # picks, never learns
    precision=(0.0, 5e-2)))


def test_the_builder_runs_the_models_first_layers(rehearsal):
    module, cfg, mcfg, params, _ = rehearsal
    assert module.layer_types(cfg) == mcfg.layer_types == (
        "channel_delta", "channel_delta", "channel_delta", "latent_attention")
    assert module.layer_types({**cfg, "num_hidden_layers": 5})[4] == "channel_delta"
    assert mcfg.kinds() == (("delta", "dense"), ("delta", "moe"), ("delta", "moe"),
                            ("latent", "moe"))
    assert mcfg.rope_theta is None and mcfg.n_experts == 16 and mcfg.experts_held == 4
    assert set(params) == set(cd.layouts(mcfg))


# ---------------------------------------------------------------------------
# the family with the rule's kernels in the interpreter
# ---------------------------------------------------------------------------

#: the tiny preset at heads the kernels tile: two chunks of 64 a sequence, the
#: state crossing a grid step of the walks
TILING = dict(lin_heads=2, lin_k_dim=128, lin_v_dim=128, chunk=64, max_seq=128)


@pytest.fixture()
def kernels_in_the_interpreter(monkeypatch):
    monkeypatch.setattr(cd, "chunked_gated_delta_rule", functools.partial(
        gd.chunked_gated_delta_rule, interpret=True, blocks=(2, 1, 1)))


@pytest.mark.parametrize("policy, inverses, walks", [
    ("family", 1, 2), ("every_name", 1, 1), ("none", 2, 2)])
def test_a_rebuilt_delta_layer_runs_the_inverse_once(
        kernels_in_the_interpreter, monkeypatch, policy, inverses, walks):
    """The gradient through the stack as ``_hidden`` walks it (``mf.walk``: each
    delta mixer under a ``jax.checkpoint`` that keeps, by name, ``DELTA_KEPT`` —
    the rule's triangular inverse and o) holds one ``kda_chunk_inverse`` and one
    ``kda_scan_bwd`` a delta layer, and ``kda_scan_fwd`` twice: the chunks'
    entering states are left to the rebuild (the cell's memory,
    ``channel_delta_moe.DELTA_KEPT``).  With every name of
    ``gated_delta.CHANNEL_SAVED`` kept, each kernel once; a delta mixer under a
    checkpoint with no policy, both forward kernels twice."""
    cfg = cd.tiny_channel_delta_moe(**TILING)
    assert cfg.remat and gd.CHANNEL_SAVED == kk.SAVED == (
        "gdn_channel_inverse", "gdn_channel_entering", "gdn_channel_out")
    assert cd.DELTA_KEPT == ("gdn_channel_inverse", "gdn_channel_out")
    params, tokens, _ = _state(cfg, batch=1)
    if policy == "none":
        layers = 1
        lp = {k: v[0] for k, v in cd.mf.stack_of(params, "delta").items()}
        mixer = jax.checkpoint(functools.partial(
            cd._delta_mixer, dataclasses.replace(cfg, remat=False)))
        grad = jax.grad(lambda x, lp: jnp.sum(jnp.sin(mixer(x, lp).astype(jnp.float32))),
                        argnums=(0, 1))
        args = (jax.random.normal(jax.random.PRNGKey(2), (1, cfg.max_seq, cfg.d_model)), lp)
    else:
        if policy == "every_name":
            monkeypatch.setattr(cd, "DELTA_KEPT", gd.CHANNEL_SAVED)
        layers = cfg.layer_types.count("channel_delta")
        grad = jax.grad(lambda p: jnp.sum(jnp.sin(cd._hidden(cfg, p, tokens)[0].astype(jnp.float32))))
        args = (params,)
    assert fc._kernel_names(grad, *args) == sorted(
        ([kk.INVERSE_KERNEL] * inverses + [kk.FWD_KERNEL] * walks + [kk.BWD_KERNEL]) * layers)


def test_the_tiny_step_with_the_kernels_matches_the_plain_reference(kernels_in_the_interpreter):
    """The tiny model's loss and every leaf's gradient against the plain
    reference, as with XLA's form of the rule
    (tests/test_channel_delta_moe.py), counting the kernels' traces alone.
    The loss over the family's own logits, outside ``shard_map``: the Pallas
    INTERPRETER's grid loop carries its scratch as unvarying (a chip's Mosaic
    has no such loop)."""
    cfg = cd.tiny_channel_delta_moe(**TILING, layer_types=("channel_delta", "latent_attention"))
    params, tokens, targets = _state(cfg, batch=1)

    def loss(p):
        logits = cd.local_logits(cfg, p, tokens)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)

    before = fc.bps.get_robustness_counters()
    got_loss, grads = jax.jit(jax.value_and_grad(loss))(params)
    after = fc.bps.get_robustness_counters()
    assert after["gdn_channel_kernel_traces"] > before.get("gdn_channel_kernel_traces", 0)
    assert after.get("gdn_channel_xla_traces", 0) == before.get("gdn_channel_xla_traces", 0)
    want_loss, want = fc._reference(ref, cfg)(params, tokens, targets)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert set(grads) == set(want)
    for name, g in grads.items():
        assert bool(jnp.any(g)) == (not name.endswith("router_bias")), name
    off, leaf = fc._worst(grads, want)
    assert off < 2e-4, f"{leaf} is {off:.2e} of its gradient off the reference's"
