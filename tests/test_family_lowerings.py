"""What ``transformer.build_train_step`` lowers to, family by family, and what
``models/moe_family.py`` decides for the MoE families on its own.

The guard (ISSUE 47): a change that means to move no program shows it here
before the chip is asked.  Three things are held for every MoE family, all
frozen on the parent of the PR that wrote ``moe_family.py`` and the same after
it: the StableHLO text of the tiny train step at float32 AND at bfloat16 (the
dtype every cell computes in; at float32 an ``.astype`` is a no-op that leaves
no trace in the text, so only the bfloat16 text sees a cast that moved), the
parameters a seed gives, and how many operations the step files under each of
the ``jax.named_scope`` names that the benchmark's readers sort every
``train_step.*_ms`` metric by.  A change that means to move one re-freezes it
here and says so.
"""

import ast
import functools
import glob
import hashlib
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import (block_diffusion_moe, channel_delta_moe, conv_moe, cross_decoder,
                               delta_moe, early_route_moe, latent_moe, looped_dense, ssm_moe,
                               window_moe)
from byteps_tpu.models import moe_family as mf
from byteps_tpu.models import transformer as tfm
from byteps_tpu.parallel.mesh_utils import make_training_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILIES = {"latent_moe": latent_moe, "delta_moe": delta_moe, "conv_moe": conv_moe,
            "window_moe": window_moe, "early_route_moe": early_route_moe, "ssm_moe": ssm_moe,
            "looped_dense": looped_dense, "block_diffusion_moe": block_diffusion_moe,
            "cross_decoder": cross_decoder, "channel_delta_moe": channel_delta_moe}
#: family → the reader of benchmark/readers/ its cell's metrics go through,
#: where that is not one of its own name
READERS = {"early_route_moe": "window_moe"}
#: family → a second reader, whose scopes the accepted metrics that list the
#: family's cell read it by: the block-diffusion family's own reader knows its
#: two scopes, its router and experts are read through ``delta_moe``'s
ALSO_READ_BY = {"block_diffusion_moe": "delta_moe"}

#: sha256 of the StableHLO text of one tiny train step (sgd 1.0, batch 2, no
#: donation, one CPU device).  The four float32 digests of ``bert``,
#: ``latent_moe``, ``delta_moe`` and ``conv_moe`` are the ones that
#: tests/test_{delta,conv,window}_moe_pieces.py froze (PRs 36, 40, 42; PR 45
#: moved ``delta_moe``); ``window_moe`` and the bfloat16 ones were taken on the
#: parent of PR 47.  ``early_route_moe``'s were taken when PR 48 wrote the
#: family; the nine above stood through the seam that PR opened in
#: ``held_expert_mlp``, ``routed_mlp`` and ``walk``.  PR 49 meant to move the
#: five MoE families' steps and only those (``held_expert_apply`` walks a
#: first chunk of 9/8 of the even load and a loop of tail chunks, and counts
#: the rows it walked as a fifth routing statistic): their ten digests and the
#: ``moe_experts`` counts below were taken again on its tree; ``bert``'s
#: digest and all five ``FROZEN_PARAMETERS`` stood.  PR 50 meant to move
#: ``latent_moe``'s step and only that (its mixer writes four token-major
#: products from weight columns in ``ops/mla_heads.py``'s order and builds the
#: flash kernel's operands in that file's pass; ``merge_heads`` is the output
#: projection): its two digests and its ``mla_attention`` count below were
#: taken again on its tree; the nine other digests and all five
#: ``FROZEN_PARAMETERS`` stood (the column order is applied in the step, not
#: to a leaf).  ``ssm_moe``'s were taken when PR 51 wrote the family; the eleven
#: above stood through the seam that PR opened in ``held_expert_apply`` and
#: ``routed_mlp`` (an absent gate matrix is data: ``w_gate`` None, no
#: ``e_gate`` | ``s_gate`` leaf).  ISSUE 56 meant to move the five families'
#: steps on ``moe_family.xent_sums`` and only those (the blocked loss takes a
#: block's gradient while its logits stand, behind a ``custom_vjp``; and in
#: ``early_route_moe`` alone a sliding layer's q | k products stay token-major
#: for ``ops/head_norm.head_rope`` and the embedding's gather is
#: ``parallel/moe.take_rows``): their ten digests, their ``lm_head`` counts and
#: ``early_route_moe``'s ``embed`` and ``window_attention`` counts below were
#: taken again on its tree; ``latent_moe``'s and ``bert``'s digests, every
#: other count and all six ``FROZEN_PARAMETERS`` stood.  ``looped_dense``'s were
#: taken when ISSUE 57 wrote the family — the first without experts; all
#: thirteen above, every count and all six ``FROZEN_PARAMETERS`` stood through
#: what that PR did to ``moe_family``: ``Family`` | ``ExpertFamily``, and ONE
#: blocked loss under ``xent_sums`` and ``weighted_xent`` (a weight a row is
#: data: ``ws`` None, no product with it; what the logits read is a tuple).
#: ISSUE 60 meant to move ``delta_moe``'s step and only that (a period's linear
#: layers are a Python loop over slices of the stacked ``lin`` leaves, no inner
#: ``lax.scan``): its two digests were taken again on its tree; the fifteen
#: other digests, every count and all eight ``FROZEN_PARAMETERS`` stood —
#: ``build_train_step`` commits an uncommitted optimizer state on the host at
#: the first CALL, and ``lower`` is the compiled function's own.
#: ISSUE 62 meant to move ``ssm_moe``'s step and only that: the Mamba-2 mixer
#: takes x, B and C each from their own columns of the convolution (no one
#: array of the three is written to be cut again) and the grouped gated norm's
#: statistics go over and come from their runs of channels as selects on the
#: channel's run (``ssm_moe.over_runs`` | ``sum_runs``: on a TPU the reshape
#: form is two copies of 268 MB a call) — its two digests and its ``ssd_scan``
#: count below were taken again on its tree; on the CPU the scan itself takes
#: XLA's form as before (``ops/ssd._kernel_path``) and nothing carries a
#: ``checkpoint_name``.  The fifteen other digests, every other count and all
#: eight ``FROZEN_PARAMETERS`` stood through ``moe_family.walk``'s new third
#: argument (stack → the names its rebuild keeps).
#: ISSUE 64 (the convolution + silu + l2 norm before the linear mixers as
#: ``ops/causal_conv.conv_silu``) moved FOUR on purpose, taken again on its
#: tree: ``delta_moe``'s two — ``_conv_rounded`` and its hand-written backward
#: pass are gone, the convolution stays f32 up to its one rounding after the
#: norm, and ``gdn_scan`` holds 1484 operations where it held 1580 — and
#: ``ssm_moe``'s two, by the ORDER of three slices alone (the taps' and the
#: bias' columns are cut before the projection's, as the call's arguments;
#: ``ssd_scan`` still holds 1750).  The fifteen others stand — ``conv_moe``'s
#: two among them (``moe_family.causal_conv`` is not edited), and
#: ``cross_decoder``'s two, whose call cuts nothing before the projection.
FROZEN_LOWERINGS = {
    ("bert", "float32"): "4749126c30bbafacbac2acbde40fdf1c9a70436ee18c993510b931cde25b1bd9",
    ("latent_moe", "float32"): "2f39501233c5fb12999aad5f6244e64071b14dda6ce394942f3ffbfe3e4ad237",
    ("delta_moe", "float32"): "b52cbde34fe72fa883d77167df632f0fc3ef8d79487d21591ce02e8001ca8db4",
    ("conv_moe", "float32"): "3c848278b7f215d90da5af8be1453d60157b992152875c775e5cfd8127ac5ea6",
    ("window_moe", "float32"): "5fa6c86a602cc5b537a9cee5e9dce944f6c84180fa3a0d986aad9c8d89ddfb5c",
    ("latent_moe", "bfloat16"): "da39e2ae36751672fca8343047e2e8a663c2bb73ef2db55a0bf153b15a600c57",
    ("delta_moe", "bfloat16"): "e448238eba378112f60b864050ec662a93d160f125a64f28c15a2e236de3b66a",
    ("conv_moe", "bfloat16"): "cb18f95a6e3209e62567e45b5d5bb60c0590b4e58a14ccb5a7cdc7289adcf63f",
    ("window_moe", "bfloat16"): "4454b585256577f84c672ad7f23299f90fe8cf25de2ee0f1ad3884d0713b9bfa",
    ("early_route_moe", "float32"): "3ad9b4b5455c7129e73f776a03785f1dcdf59f349978b9d8985ef137a5b969b2",
    ("early_route_moe", "bfloat16"): "606fbf2469a7d2d98009ea2d4af5aaa3c74e595dae7fe215bb6c60c402534b23",
    ("ssm_moe", "float32"): "b45811d01d216d79c88007fba520ff87229ac5ecf4762cc20d2eb5f033be1057",
    ("ssm_moe", "bfloat16"): "e3c0f1554ecdd1f4db92ad12b096bb85f4a495472295b0f610f177fa174d8d2e",
    ("looped_dense", "float32"): "8f77ef7cd0b04aa67647a10ba8b7aef4b656ff5aab136dcee546c5ebabcd3915",
    ("looped_dense", "bfloat16"): "b4715038548ff6df5a843859cbce33e92e88f5735e974d20a5a0dbb0057324cd",
    # taken when ISSUE 59 wrote the family — the first whose step takes a third
    # leaf of the batch; the fifteen above, every count and all seven
    # ``FROZEN_PARAMETERS`` stood through what that PR did to
    # ``build_train_step`` (a family that declares no ``batch_leaves`` lowers
    # to the text it did) and to ``ops/flash_attention.py`` (a third pair of
    # kernels beside the two)
    ("block_diffusion_moe", "float32"):
        "1aeb34d192f5164aa8e365f70e07c547a591c012826be5cb8a06c77d378f9ce2",
    ("block_diffusion_moe", "bfloat16"):
        "0e5298b98e47989502e1d2fdf991ef436b05f568401091cce5a36b0f4ccf1ab6",
    # taken when ISSUE 63 wrote the family — the first on ``moe_family.Patterned``
    # without experts, the first whose parts hand values forward
    # (``moe_family.Carried``); the seventeen above, every count and all eight
    # ``FROZEN_PARAMETERS`` stood through ``Patterned``'s split from
    # ``ExpertFamily`` and ``walk``'s ``takes`` | ``carried``
    ("cross_decoder", "float32"):
        "f7b54ed6a2262764759d51bf30d25c96639b74fd62fbb4e0af5c0c9b8b150ab1",
    ("cross_decoder", "bfloat16"):
        "55587533d5913f24b270d8e739d224e80705f1fb9a26b8c37975675d82eb28cd",
    # taken when ISSUE 68 wrote the family — the delta rule with a decay a key
    # channel, latent attention with neither query bottleneck nor positions;
    # the nineteen above, every count and all nine ``FROZEN_PARAMETERS`` stood
    # through what that PR did to the shared code: ``latent_moe``'s mixer is
    # ``moe_family.latent_attention`` (the same operations in the same order;
    # a query bottleneck is a leaf that is there, a rope a ``theta`` that is
    # not None), ``ops/mla_heads.py`` makes its tables in ``_tables``, and
    # ``ops/gated_delta.py`` takes the channel form only where g has a
    # channel's dim
    ("channel_delta_moe", "float32"):
        "74c43b3804f9ecbe792d63ebb8c1672b6b2d3f15f3f0081bd8374321e50dda04",
    ("channel_delta_moe", "bfloat16"):
        "3d77ebd17a3850f81856d925e870bcd25001b6198dac841ad5f962595403f0c7",
}

#: sha256 over ``init_params(tiny_<family>(), PRNGKey(0))``: every leaf's name,
#: shape, dtype and bytes, in the order of ``layouts()``
FROZEN_PARAMETERS = {
    "latent_moe": "bd1836107f6ad448e2b1c3cd8d3fe4f55ce57caeb2400e3d5daf9907a6f7cb79",
    "delta_moe": "8bfcfa5a4b01a8a44f0f934ae49c063ca1c23a8706ea4bdcfc2271d7642e55ba",
    "conv_moe": "f4fc219077bd48be16757941eb58ce12bd017c8d91e938d818e638eccd7d39b4",
    "window_moe": "3d45bbea9a827d634d53bc91e00efbd1c80f4f58fcc242b9d196fc2d1b06a46e",
    "early_route_moe": "4185b5dc3604eb74127f4bad08f21f9bc5d63374e23957bc4be2b2751ced5698",
    "ssm_moe": "f490c4e980eef04431d28a3f03db0f49592603f5f02f9153b547ad90a9220f01",
    "looped_dense": "0b4e0693777702fb56a74903a1a7c62bdf5c74d861676882e23cca3fff028e97",
    "block_diffusion_moe": "ce733faf1c2e8c92691f1c00b4099bb1d77ac078e40cc86c2f5e229fde94a92b",
    "cross_decoder": "c52348f3776f8cca65570fe990e5659bcf3307ca0077bb28cabe5c5f0397ad73",
    "channel_delta_moe": "5c82e8a945814760664f82c66e5efe068903a056fa7b888b7369a29f03db50ee",
}

#: family → scope → operations of the bfloat16 step filed under it.  The scopes
#: and their order are ``SCOPES`` of benchmark/readers/<family>.py (held equal
#: below); an operation is filed under the first of them that its scope path
#: has as a segment, which is the readers' rule.  ``early_route_moe`` goes
#: through ``window_moe``'s reader and has four of its six scopes: no dense
#: layer and no shared expert; its ``moe_route`` holds the held experts' plan
#: (the sort) beside the router, its ``moe_experts`` no sort.  ``ssm_moe``'s
#: layers are one part each: two Mamba-2 mixers (``ssd_scan`` between the two
#: ``ssm_proj``), one attention layer, two expert layers.
FROZEN_SCOPE_OPERATIONS = {
    "latent_moe": {"mtp": 578, "mla_attention": 1938, "moe_route": 154, "moe_experts": 1002,
                   "moe_shared": 80},
    "delta_moe": {"gdn_scan": 1484, "gdn_proj": 105, "gated_attention": 584, "moe_route": 172,
                  "moe_experts": 1002, "moe_shared": 164},
    "conv_moe": {"short_conv": 369, "conv_proj": 258, "gqa_attention": 534, "dense_mlp": 105,
                 "moe_route": 231, "moe_experts": 1488},
    "window_moe": {"window_attention": 1884, "global_attention": 456, "dense_mlp": 165,
                   "moe_route": 231, "shared_expert": 132, "moe_experts": 1947},
    "early_route_moe": {"window_attention": 1254, "global_attention": 222, "moe_route": 412,
                        "moe_experts": 2104},
    "ssm_moe": {"ssd_scan": 1750, "ssm_proj": 174, "nope16_attention": 221, "moe_route": 152,
                "shared_expert": 64, "moe_experts": 936},
    # the looped family's four scopes; ``loop_heads`` holds the blocked loss's
    # ``lm_head`` inside it.  What stands under ``loop_steps`` and under none of
    # them — the reader's ``loop_carry`` — and ``embed`` are held below
    "looped_dense": {"exit_gate": 126, "loop_heads": 109, "loop_attention": 470, "loop_mlp": 163},
    # three layers, the last on the noisy half alone; ``copies_assembly`` is the
    # ids' concatenation and the last layer's cut with its transpose
    "block_diffusion_moe": {"block_diffusion_attention": 1904, "copies_assembly": 4,
                            "moe_route": 261, "moe_experts": 1683},
    # a whole model of 8 layers: three Mamba-1 mixers, two window layers, the
    # full layer, one GMU, one cross layer, eight SwiGLUs (ISSUE 63)
    "cross_decoder": {"selective_scan": 435, "mamba_proj": 1027, "diff_window_attention": 1536,
                      "diff_full_attention": 755, "diff_cross_attention": 598,
                      "gated_memory": 141, "dense_mlp": 1152, "lm_head": 59, "embed": 17},
    # three layers, every kind once: two delta mixers (``kda_scan`` between the
    # two ``kda_proj``; the chunked rule with its sub-blocks is most of it), one
    # latent layer without positions, one dense MLP, two expert layers (ISSUE 68)
    "channel_delta_moe": {"kda_scan": 3326, "kda_proj": 292, "nope_latent_attention": 433,
                          "dense_mlp": 105, "moe_route": 152, "moe_experts": 988,
                          "moe_shared": 80},
}

#: family → scope → operations of the step (bfloat16; ``bert``'s float32 one)
#: filed under the scopes that ISSUE 54 opened around what the layers' own
#: scopes left unnamed, by the rule and in the order of
#: benchmark/readers/step_rest.py's ``SCOPES``: ``lm_head`` — the final norm,
#: the head's product, logsumexp, the gold logit, the masked sum and their
#: backward pass (``latent_moe``'s holds its MTP module's head too, under
#: ``…/mtp/…/lm_head``) —, ``embed`` — the gather with its cast, scale or
#: learned positions and its scatter-add —, and ``dense_mlp`` in
#: ``latent_moe`` (``conv_moe``'s and ``window_moe``'s stand above: their own
#: readers file them).  Taken on ISSUE 54's tree; every digest, parameter and
#: count above stood: the scopes moved locations, not text.  The five
#: ``xent_sums`` families' ``lm_head`` counts (62 → 53: one loop over the
#: blocks, not two) and ``early_route_moe``'s ``embed`` (16 → 41: the sort and
#: the sorted scatter-add of ``parallel/moe.add_rows``) are ISSUE 56's.
FROZEN_REST_OPERATIONS = {
    "bert": {"lm_head": 111, "embed": 46},
    "conv_moe": {"lm_head": 53, "embed": 17},
    "delta_moe": {"lm_head": 53, "embed": 16},
    "early_route_moe": {"lm_head": 53, "embed": 41},
    "latent_moe": {"lm_head": 319, "embed": 17, "dense_mlp": 106},
    "ssm_moe": {"lm_head": 53, "embed": 16},
    "window_moe": {"lm_head": 53, "embed": 20},
    # the blocked loss with a weight a row (ISSUE 59)
    "block_diffusion_moe": {"lm_head": 61, "embed": 16},
    "channel_delta_moe": {"lm_head": 53, "embed": 16},
}


@functools.cache
def _lowered(family: str, dtype: str):
    if family == "bert":
        cfg = tfm.tiny_test(causal=False)
        params = tfm.init_params(cfg)
    else:
        module = FAMILIES[family]
        cfg = getattr(module, f"tiny_{family}")(compute_dtype=jnp.dtype(dtype))
        params = module.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_training_mesh(1, {"dp": 1, "pp": 1, "sp": 1, "tp": 1}, devices=jax.devices()[:1])
    tx = optax.sgd(1.0)
    tokens = jnp.zeros((2, cfg.max_seq), jnp.int32)
    # the leaves a family declares beyond (tokens, targets): f32, a row's shape
    more = (jnp.ones(tokens.shape, jnp.float32),) * len(getattr(cfg, "batch_leaves", ()))
    return tfm.build_train_step(cfg, mesh, tx, donate=False).lower(
        params, tx.init(params), tokens, tokens, *more)


@pytest.mark.parametrize("family,dtype", sorted(FROZEN_LOWERINGS),
                         ids=["-".join(case) for case in sorted(FROZEN_LOWERINGS)])
def test_the_steps_lower_as_before(family, dtype):
    text = _lowered(family, dtype).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == FROZEN_LOWERINGS[family, dtype]


@pytest.mark.parametrize("family, dp, tp", [("bert", 1, 1), ("bert", 2, 2)] + [
    (family, 1, 1) for family in sorted(FAMILIES)])
def test_the_step_compiles_one_program(family, dp, tp):
    """The state made as the builders and the examples make it,
    ``jax.jit(tx.init)(params)``: adamw's leaves come back uncommitted on one
    device, and the step returns them on the mesh.  The step commits them at
    its first call (ISSUE 60), so three calls hold ONE entry in the jitted
    step's cache; what the first call lowers is, text for text, what a call on
    the returned state lowers — the program every later step runs —, and a
    state that arrives committed is handed on as it is, leaf for leaf.
    ``bert``'s layouts name ``pp`` and ``tp`` on any mesh: a spec comes back
    without its axes of size one, so that is how parameters and state go in
    (one chip, and two data-parallel ranks of two tensor-parallel devices)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if family == "bert":
        cfg = tfm.tiny_test(causal=False)
        params = tfm.init_params(cfg)
    else:
        cfg = getattr(FAMILIES[family], f"tiny_{family}")()
        params = FAMILIES[family].init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_training_mesh(dp * tp, {"dp": dp, "pp": 1, "sp": 1, "tp": tp},
                              devices=jax.devices()[:dp * tp])
    placed = {k: NamedSharding(mesh, spec) for k, spec in tfm.param_specs(cfg).items()}
    params = jax.device_put(dict(params), placed)
    tokens = jax.device_put(jnp.zeros((2 * dp, cfg.max_seq), jnp.int32),
                            NamedSharding(mesh, P("dp", "sp")))
    batch = (tokens, tokens) + (jnp.ones(tokens.shape, jnp.float32),) * len(
        getattr(cfg, "batch_leaves", ()))
    tx = optax.adamw(1e-3)
    state = jax.jit(tx.init)(params)
    assert not any(leaf.committed for leaf in jax.tree.leaves(state))
    step = tfm.build_train_step(cfg, mesh, tx, donate=False)
    committed = step._first(params, state, *batch)
    assert jax.tree.structure(committed[1]) == jax.tree.structure(state)
    assert all(leaf.committed and leaf.sharding.mesh == mesh
               for leaf in jax.tree.leaves(committed[:2]))
    # the same placement under jax's own name for it; nothing moved
    assert all(committed[0][k].sharding.is_equivalent_to(placed[k], params[k].ndim)
               for k in params)
    moments = committed[1][0]  # adamw: (ScaleByAdamState, …)
    assert all(moments.mu[k].sharding == moments.nu[k].sharding == committed[0][k].sharding
               for k in params)
    assert moments.count.sharding.spec == P()
    again = step._first(*committed)
    assert all(a is b for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(committed)))
    first = step.lower(*committed).as_text()
    for _ in range(3):
        params, state, _ = step(params, state, *batch)
        assert step._jitted._cache_size() == 1
    assert step.lower(params, state, *batch).as_text() == first


def parameters_digest(params) -> str:
    digest = hashlib.sha256()
    for name, leaf in params.items():
        leaf = np.asarray(leaf)
        digest.update(f"{name} {leaf.shape} {leaf.dtype}\n".encode())
        digest.update(leaf.tobytes())
    return digest.hexdigest()


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_seed_gives_the_parameters_it_gave(family):
    module = FAMILIES[family]
    cfg = getattr(module, f"tiny_{family}")()
    params = module.init_params(cfg, jax.random.PRNGKey(0))
    assert list(params) == list(cfg.layouts())
    assert parameters_digest(params) == FROZEN_PARAMETERS[family]


_NAMED_LOC = re.compile(r'^(#loc\d+) = loc\("([^"]*)"\(#loc\d+\)\)$', re.M)
_OPERATION = re.compile(r"^(?!#loc).* loc\((#loc\d+)\)$", re.M)


def scope_operations(text: str, scopes) -> dict:
    """scope → lines of ``text`` (StableHLO with debug info) whose location is
    a scope path with that scope the first of ``scopes`` among its segments."""
    paths = dict(_NAMED_LOC.findall(text))
    counts = dict.fromkeys(scopes, 0)
    for loc in _OPERATION.findall(text):
        parts = paths.get(loc, "").split("/")
        scope = next((s for s in scopes if s in parts), None)
        if scope is not None:
            counts[scope] += 1
    return counts


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_reader_{name}", os.path.join(REPO, "benchmark", "readers", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _readers_scopes(family: str) -> tuple:
    return tuple(_reader(family).SCOPES)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_scope_the_readers_file_by_holds_its_operations(family):
    want = FROZEN_SCOPE_OPERATIONS[family]
    known = _readers_scopes(READERS.get(family, family))
    if family in ALSO_READ_BY:
        known += tuple(s for s in _readers_scopes(ALSO_READ_BY[family]) if s in want)
    # a family's own reader knows just its scopes; one that borrows a reader
    # has some of that reader's, in its order
    assert tuple(want) == (tuple(s for s in known if s in want) if family in READERS else known)
    got = scope_operations(_lowered(family, "bfloat16").as_text(debug_info=True), tuple(want))
    for scope in want:  # by name: a scope that lost its last operation says which
        assert got[scope] > 0, f"{family}: no operation is filed under {scope}"
    assert got == want


@pytest.mark.parametrize("family", sorted(FROZEN_REST_OPERATIONS))
def test_the_head_the_loss_and_the_embedding_stand_under_scopes(family):
    rest = _reader("step_rest")
    want = FROZEN_REST_OPERATIONS[family]
    text = _lowered(family, "float32" if family == "bert" else "bfloat16").as_text(debug_info=True)
    got = scope_operations(text, rest.SCOPES)
    own = FROZEN_SCOPE_OPERATIONS.get(family, {})  # conv_moe's and window_moe's dense_mlp
    assert {s: n for s, n in got.items() if n and s not in own} == want
    assert all(got[s] == own[s] for s in own if s in got)
    # no operation is under two of the names a step's time is added up from,
    # but for the MTP module's head, which its family's reader files under mtp
    paths = dict(_NAMED_LOC.findall(text))
    family_scopes = set(own)
    twice = [p for p in (paths.get(loc, "").split("/") for loc in _OPERATION.findall(text))
             if len((family_scopes | set(want)).intersection(p)) > 1]
    assert all("mtp" in p and "lm_head" in p for p in twice)
    assert bool(twice) == (family == "latent_moe")


# ---------------------------------------------------------------------------
# What moe_family.py decides on its own
# ---------------------------------------------------------------------------


#: operations of ``looped_dense``'s bfloat16 step under ``loop_steps`` and none
#: of the scopes inside it, and under ``embed``
LOOP_CARRY_OPERATIONS, LOOPED_EMBED_OPERATIONS = 73, 39


def test_the_looped_family_files_its_loop_and_its_embedding():
    """Beside its four scopes: every operation under ``loop_steps`` is read
    under one of the reader's five names (``loop_carry`` where no scope inside
    the loop holds it), ``embed`` is outside the loop, and the blocked loss's
    ``lm_head`` is nowhere but inside ``loop_heads``."""
    reader = _reader("looped_dense")
    text = _lowered("looped_dense", "bfloat16").as_text(debug_info=True)
    paths = dict(_NAMED_LOC.findall(text))
    filed = {}
    for loc in _OPERATION.findall(text):
        path = paths.get(loc, "")
        name = reader.scope_of(path)
        filed[name] = filed.get(name, 0) + 1
        parts = path.split("/")
        assert ("lm_head" not in parts) or name == "loop_heads", path
        assert ("embed" not in parts) or (name is None and reader.LOOP not in parts), path
        assert (reader.LOOP not in parts) or name is not None, path
    assert {k: n for k, n in filed.items() if k} == {
        **FROZEN_SCOPE_OPERATIONS["looped_dense"], reader.CARRY: LOOP_CARRY_OPERATIONS}
    assert scope_operations(text, ("embed",)) == {"embed": LOOPED_EMBED_OPERATIONS}


def test_no_family_imports_another():
    """Every ``*_moe`` module — and the looped dense family — leans on
    ``moe_family`` and on no sibling."""
    models = os.path.join(REPO, "byteps_tpu", "models")
    for path in glob.glob(os.path.join(models, "*_moe.py")) + [
            os.path.join(models, "looped_dense.py")]:
        imported = set()
        with open(path) as source:
            tree = ast.parse(source.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                imported |= {node.module or ""} | {f"{node.module}.{a.name}" for a in node.names}
            elif isinstance(node, ast.Import):
                imported |= {a.name for a in node.names}
        siblings = sorted(name for name in imported if name.split(".")[-1].endswith("_moe"))
        assert not siblings, f"{os.path.basename(path)} imports {siblings}"


def test_init_refuses_a_leaf_without_a_rule():
    layout = mf.layouts({"embed": (6, 4), "norm_f": (4,)}, {"moe": (2, {"router": (4, 3)})})
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="norm_f"):
        mf.init_params(layout, key, {})
    params = mf.init_params(layout, key, {"*norm*": mf.zeros, "norm_f": mf.ones})
    assert list(params) == ["embed", "norm_f", "moe.router"]
    assert params["moe.router"].shape == (2, 4, 3)
    np.testing.assert_array_equal(params["norm_f"], np.ones(4))  # the exact name, not the pattern
    np.testing.assert_array_equal(  # leaf 2 of the layout, fan-in the dim before the last
        params["moe.router"], 0.5 * jax.random.normal(jax.random.fold_in(key, 2), (2, 4, 3)))


def test_the_walk_runs_the_listed_layers_in_order():
    cfg = conv_moe.tiny_conv_moe(
        layer_types=("conv", "full_attention", "conv"), n_dense_layers=2, remat=False)
    params = {f"{stack}.w": 10.0 * (i + 1) + jnp.arange(3.0)  # layer l of a stack holds 10·i + l
              for i, stack in enumerate(("conv", "attn", "dense", "moe"))}
    ran = []

    def part(stack):
        def run(x, lp):
            ran.append((stack, float(lp["w"])))
            return (x + 1, jnp.arange(5, dtype=jnp.int32)) if stack == "moe" else x + 1
        return run

    x, stats = mf.walk(cfg, {s: part(s) for s in ("conv", "attn", "dense", "moe")}, ("attn",),
                       params, jnp.zeros(()))
    assert ran == [("conv", 10.0), ("dense", 30.0), ("attn", 20.0), ("dense", 31.0),
                   ("conv", 11.0), ("moe", 40.0)]
    assert float(x) == 6 and list(stats) == [0, 1, 2, 3, 4]


def test_the_blocked_loss_is_the_unblocked_one(monkeypatch):
    """14 rows in blocks of gcd(14, 4) = 2, some targets ignored."""
    monkeypatch.setattr(mf, "ROW_BLOCK", 4)
    cfg = window_moe.tiny_window_moe()
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 7, cfg.d_model)), jnp.float32)
    scale = jnp.asarray(1 + 0.1 * rng.normal(size=cfg.d_model), jnp.float32)
    head = jnp.asarray(rng.normal(size=(cfg.vocab_size, cfg.d_model)), jnp.float32)
    targets = jnp.asarray(rng.integers(-1, cfg.vocab_size, size=(2, 7)), jnp.int32).at[0, 0].set(-1)

    def blocked(x, scale, head):
        return mf.xent_sums(cfg, mf.row_logits, x, targets, scale, head)[0]

    def whole(x, scale, head):
        logits = mf.row_logits(cfg, x, scale, head)
        gold = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * (targets >= 0))

    got, want = (jax.value_and_grad(f, argnums=(0, 1, 2))(x, scale, head) for f in (blocked, whole))
    assert float(mf.xent_sums(cfg, mf.row_logits, x, targets, scale, head)[1]) == int(
        jnp.sum(targets >= 0)) < 14
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# The blocked loss takes its gradient on the way up (ISSUE 56)
# ---------------------------------------------------------------------------


def _plain_sum(cfg, logits, x, targets, scale, head):
    """The unblocked loss: every row's logits at once."""
    rows = logits(cfg, x, scale, head)
    gold = jnp.take_along_axis(rows, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum((jax.nn.logsumexp(rows, axis=-1) - gold) * (targets >= 0))


def _checkpointed_sum(cfg, logits, x, targets, scale, head):
    """The blocked form this tree's parent ran: a ``jax.checkpoint``ed block
    under ``lax.map``, rebuilt in the backward pass."""
    d = x.shape[-1]
    block = math.gcd(x.size // d, mf.ROW_BLOCK)
    one = jax.checkpoint(lambda xb, tb: _plain_sum(cfg, logits, xb, tb, scale, head))
    return jnp.sum(jax.lax.map(lambda xt: one(*xt),
                               (x.reshape(-1, block, d), targets.reshape(-1, block))))


#: family → (the ``logits`` its ``local_loss`` hands ``xent_sums``, the leaf
#: that is its head, whether that lies (model, vocabulary)): the five callers.
#: ``latent_moe`` keeps its own blocked loss (its configuration states the
#: logits' recomputation) and is not among them.
HEADS = {"early_route_moe": (mf.row_logits, "head", False),
         "ssm_moe": (mf.row_logits, "head", False),
         "window_moe": (mf.row_logits, "head", False),
         "conv_moe": (mf.row_logits, "embed", False),  # the tied embedding
         "delta_moe": (delta_moe._logits, "head", True)}


def _head_case(family: str, dtype: str, seed: int = 56):
    """14 rows in blocks of gcd(14, 4) = 2: rows of both sequences ignored and
    the third block (rows 4, 5) ignored whole.  x is rounded to ``dtype``
    once, here; the scale and the head are f32 leaves, as a step's are."""
    cfg = getattr(FAMILIES[family], f"tiny_{family}")(compute_dtype=jnp.dtype(dtype))
    logits, leaf, model_major = HEADS[family]
    assert cfg.layouts()[leaf][0] == ((cfg.d_model, cfg.vocab_size) if model_major else
                                      (cfg.vocab_size, cfg.d_model))
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, 7, cfg.d_model)), cfg.compute_dtype)
    scale = jnp.asarray(1 + 0.1 * rng.normal(size=cfg.d_model), jnp.float32)
    head = jnp.asarray(rng.normal(size=(cfg.vocab_size, cfg.d_model)) * cfg.d_model ** -0.5,
                       jnp.float32)
    targets = rng.integers(0, cfg.vocab_size, size=(2, 7))
    targets[0, [0, 4, 5]] = targets[1, 6] = -1
    return cfg, logits, x, jnp.asarray(targets, jnp.int32), scale, head.T if model_major else head


@pytest.mark.parametrize("cotangent", [1.0, 0.3], ids=["unit", "0.3_of_count"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("family", sorted(HEADS))
def test_the_blocked_loss_takes_the_plain_gradient_on_the_way_up(monkeypatch, family, dtype,
                                                                 cotangent):
    """``jax.value_and_grad`` through ``xent_sums`` — each block's ``softmax −
    onehot`` pulled back while its logits stand, the backward pass a scale —
    against the same through the plain unblocked ``logsumexp − gold`` and
    through the parent's checkpointed blocks: value, dx, d scale, d head, for
    every caller's ``logits``, at a unit cotangent and at 0.3 / count.
    float32: to 2e-6 of a leaf's largest entry.  bfloat16: the forms differ by
    where a sum is rounded to bfloat16 (a block's dx and the rounding of
    ``softmax − onehot`` before, not after, the cotangent's scale): 2⁻⁷."""
    monkeypatch.setattr(mf, "ROW_BLOCK", 4)
    cfg, logits, x, targets, scale, head = _head_case(family, dtype)
    count = int(jnp.sum(targets >= 0))
    assert count == 10 and not bool(jnp.any(targets.reshape(-1, 2)[2] >= 0))
    weight = cotangent if cotangent == 1.0 else cotangent / count

    def blocked(x, scale, head):
        total, counted = mf.xent_sums(cfg, logits, x, targets, scale, head)
        assert counted.dtype == jnp.float32
        return total * weight

    tol = 2e-6 if dtype == "float32" else 2.0 ** -7
    loss, got = jax.value_and_grad(blocked, argnums=(0, 1, 2))(x, scale, head)
    # differentiated or not, the value is the blocks' sums
    np.testing.assert_allclose(loss, blocked(x, scale, head), rtol=1e-6)
    for other in (_plain_sum, _checkpointed_sum):
        want_loss, want = jax.value_and_grad(
            lambda *at: other(cfg, logits, at[0], targets, *at[1:]) * weight,
            argnums=(0, 1, 2))(x, scale, head)
        np.testing.assert_allclose(loss, want_loss, rtol=2 * tol)
        for name, a, b in zip(("x", "scale", "head"), got, want):
            assert a.dtype == b.dtype and a.shape == b.shape, name
            a, b = (np.asarray(v, np.float32) for v in (a, b))
            assert np.abs(b).max() > 0
            np.testing.assert_allclose(a, b, rtol=tol, atol=tol * np.abs(b).max(), err_msg=name)
    # an ignored row's gradient is exactly nothing, a whole ignored block's too
    assert not np.asarray(got[0], np.float32)[0, [0, 4, 5]].any()
    assert not np.asarray(got[0], np.float32)[1, 6].any()


@pytest.mark.parametrize("family", sorted(HEADS))
def test_a_block_is_one_product_in_the_loss_and_three_with_its_gradient(family):
    """The witness: the loss alone lowers to ONE product of the head's size in
    its loop over the blocks; the loss with its gradient to THREE in that one
    loop (the logits, dx, d head) and none after it — four stood here while a
    block was rebuilt in the backward pass, in two loops."""
    cfg, logits, x, targets, scale, head = _head_case(family, "bfloat16")

    def loss(x, scale, head):
        total, count = mf.xent_sums(cfg, logits, x, targets, scale, head)
        return total / count

    alone = jax.jit(loss).lower(x, scale, head).as_text()
    both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(x, scale, head).as_text()
    assert alone.count("stablehlo.dot_general") == 1
    assert both.count("stablehlo.dot_general") == 3
    assert alone.count("stablehlo.while") == both.count("stablehlo.while") == 1


@pytest.mark.parametrize("family", ["early_route_moe", "delta_moe"])
def test_the_blocked_loss_sums_its_gradients_over_the_ranks(monkeypatch, family):
    """Under ``shard_map`` on two devices, a sequence each: the head and the
    final norm's scale are replicated where x varies, and their gradients come
    out summed over the ranks (one ``psum`` after the blocks, the transpose of
    the cast that typed them as varying) — the unsharded loss's; dx stays each
    rank's own."""
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setattr(mf, "ROW_BLOCK", 4)
    cfg, logits, x, targets, scale, head = _head_case(family, "float32")
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))

    def local(x, targets, scale, head):
        def total(x, scale, head):
            return mf.xent_sums(cfg, logits, x, targets, scale, head)[0]

        loss, grads = jax.value_and_grad(total, argnums=(0, 1, 2))(x, scale, head)
        return jax.lax.psum(loss, "dp"), grads

    sharded = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("dp"), P("dp"), P(), P()),
        out_specs=(P(), (P("dp"), P(), P())), check_vma=True))
    loss, got = sharded(x, targets, scale, head)
    want_loss, want = jax.value_and_grad(
        lambda *at: _plain_sum(cfg, logits, at[0], targets, *at[1:]), argnums=(0, 1, 2))(
            x, scale, head)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for name, a, b in zip(("x", "scale", "head"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6 * float(jnp.abs(b).max()),
                                   err_msg=name)
    text = sharded.lower(x, targets, scale, head).as_text()
    assert text.count("stablehlo.all_reduce") == 3  # the loss, d scale, d head: none a block
