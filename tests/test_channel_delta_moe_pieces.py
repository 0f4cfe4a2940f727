"""The channel-delta MoE cell's blocked reference
(benchmark/builders/kimi_linear.py) against the plain reference
(models/channel_delta_moe_reference.py) at toy widths, on the CPU: the blocking
is what is under test — the rule in runs of tokens and groups of heads, the
latent layer in groups of heads and blocks of queries, the MLPs and the logits
in blocks of rows.  (The family itself: tests/test_channel_delta_moe.py.  Two
files so that ``--dist loadfile`` spreads them.)
"""

import functools

from byteps_tpu.models import channel_delta_moe as cd
from byteps_tpu.models import channel_delta_moe_reference as ref

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
