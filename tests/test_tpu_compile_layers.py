"""The families' mixers, the recurrences' layers and whole steps compiled for
the chip at their published widths, without a chip (tests/compiled_for_tpu.py
says how): what XLA:TPU writes around the kernels — no head-major copy, no
columns cut out or padded back, each tensor moved once —, which kernels a
rebuilt layer calls and how often, what a step keeps, copies and holds of the
chip's memory.
"""

import dataclasses
import functools
import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest

from byteps_tpu.ops import _dispatch

from compiled_for_tpu import (  # noqa: F401 (fixtures)
    _bytes_of, _compile, _cuts_written_under, _kernel_operands, _named_bytes,
    _relayouts_under, _slices_moved, _sources, _top_level, no_compile_cache, one_chip)

fa = importlib.import_module("byteps_tpu.ops.flash_attention")


def test_short_conv_mixer_compiles_at_published_widths(one_chip, no_compile_cache):
    """2 x 8192 tokens, 2048 channels, 3 taps, bf16 operands: the double-gated
    short convolution between its two projections and its four gradients.
    What the backward pass keeps and makes stays a few copies of the
    (16 384, 6144) projection (201 MB in bf16), not one a tap in f32."""
    from byteps_tpu.models import conv_moe as cm

    cfg = cm.ConvMoEConfig(compute_dtype=jnp.bfloat16)
    assert (cfg.d_model, cfg.conv_kernel) == (2048, 3)
    shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)
    lp = {k: shape(*s) for k, s in cm.stacks(cfg)["conv"][1].items()}

    def loss(x, lp):
        return jnp.sum(cm._conv_mixer(cfg, x, lp).astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=(0, 1)), shape(2, 8192, 2048, dtype=jnp.bfloat16), lp)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


@pytest.mark.parametrize("implementation", ["xla", "kernels"])
def test_state_space_mixer_compiles_at_published_widths(one_chip, no_compile_cache, monkeypatch,
                                                        implementation):
    """2 x 8192 tokens, 64 heads of 64 with a 64 x 128 state, B and C in 8
    groups of 128, chunks of 128, bf16 operands: a Mamba-2 layer whole —
    ``in_proj``, the biased convolution, the chunked scan, ``D x``, the gated
    grouped norm, ``out_proj`` — and its gradients.  ``xla`` is the form
    every platform but a TPU takes (a block of chunks rebuilt at a time): what
    stands at a time stays a few copies of the (16 384, 10 304) projection
    (338 MB in bf16) and ONE block's decay matrices, not a layer's (537 MB in
    f32, and their products beside them).  ``kernels`` is a TPU's path: the
    two Pallas kernels once each, no scan left to XLA, and no f32 array of a
    decay matrix's shape anywhere."""
    from byteps_tpu.models import ssm_moe as sm
    from byteps_tpu.ops import causal_conv as cc
    from byteps_tpu.ops import ssd_kernels as sk

    if implementation == "kernels":
        monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    cfg = sm.SsmMoEConfig(compute_dtype=jnp.bfloat16)
    assert (cfg.d_model, cfg.d_inner, cfg.conv_channels, cfg.chunk) == (2688, 4096, 6144, 128)
    shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)
    lp = {k: shape(*s) for k, s in sm.stacks(cfg)["ssm"][1].items()}

    def loss(x, lp):
        return jnp.sum(sm._ssm_layer(cfg, x, lp).astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=(0, 1)),
                        shape(2, 8192, 2688, dtype=jnp.bfloat16), lp)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * 2**30
    text = compiled.as_text()
    if implementation == "kernels":
        assert sk.FWD_KERNEL in text and sk.BWD_KERNEL in text and "while" not in text
        assert not re.search(r"f32\[[\d,]*128,128,8,8\]|f32\[[\d,]*,128,128\]", text)
        # x, B and C are read out of in_proj's product by the convolution's
        # index maps (the smallest, B | C, is 32 MB): nothing of it is cut
        # out, padded or shifted under the scope
        assert cc.CONV_FWD_KERNEL in text and cc.CONV_BWD_KERNEL in text
        assert _cuts_written_under(text, "ssd_scan", 16 * 2**20) == []
    else:
        assert "while" in text and "tpu_custom_call" not in text


def test_delta_mixer_stays_token_major_at_published_widths(one_chip, no_compile_cache,
                                                           monkeypatch):
    """``_delta_mixer``'s forward and gradient for one sequence of 16 384
    tokens at Qwen3-Next's widths (2048 → 12 288 | 64, 16 | 32 heads of 128):
    from ``w_qkvz``'s product to ``w_out``'s no copy of q, k, v, z, o or a
    cotangent of theirs in another layout exists — the compiled module holds
    no ``transpose`` and no layout-changing ``copy`` of 64 MB or more under
    ``gdn_scan`` (a (16384, 2048) bf16 array is 64 MB; the parent's module
    of this case holds 17 such copies: the head-major operands and their ways
    back) —, the three kernels take token-major operands, and the temporaries
    stay under what the parent's module of the same case needs (2.63 GiB; this
    one 2.41)."""
    from byteps_tpu.models import delta_moe as dm
    from byteps_tpu.ops import causal_conv as cc
    from byteps_tpu.ops import gated_delta_kernels as gk

    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    cfg = dm.DeltaMoEConfig(compute_dtype=jnp.bfloat16)  # the published widths
    s = cfg.max_seq
    assert (s, cfg.lin_channels, cfg.lin_v_heads * cfg.lin_v_dim) == (16384, 8192, 4096)
    lp = {name: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
          for name, shape in dm.layer_shapes(cfg)["lin"].items()}
    x = jax.ShapeDtypeStruct((1, s, cfg.d_model), jnp.bfloat16, sharding=one_chip)

    def loss(x, lp):
        return jnp.sum(dm._delta_mixer(cfg, x, lp).astype(jnp.float32) ** 2)

    for fn in (loss, jax.grad(loss, argnums=(0, 1))):
        compiled = _compile(fn, x, lp)
        text = compiled.as_text()
        assert gk.FWD_KERNEL in text and gk.INVERSE_KERNEL in text
        # q | k as the kernels' operand
        assert f"bf16[1,{s},{cfg.lin_k_heads * cfg.lin_k_dim}]" in text
        assert _relayouts_under(text, "gdn_scan", 64 * 2**20) == []
        # q, k and v are read out of w_qkvz's product by the convolution's
        # index maps: no columns of it are cut out or padded back
        assert cc.CONV_FWD_KERNEL in text
        assert _cuts_written_under(text, "gdn_scan", 32 * 2**20) == []
    assert gk.BWD_KERNEL in text and cc.CONV_BWD_KERNEL in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * 2**30


# (GiB the parent's module of the case names outside its kernels, GiB this one may)
_MIXER_BYTES = {"win": (8.90, 5.8), "glob": (6.73, 5.8)}


@pytest.mark.parametrize("stack", ["win", "glob"])
def test_attention_mixer_moves_each_tensor_once_at_published_widths(one_chip, no_compile_cache,
                                                                    monkeypatch, stack):
    """``window_moe._attention_mixer``'s gradient (no recomputation) for one
    sequence of 16 384 tokens at Trinity-Mini's widths (2048 → 32 | 4 | 4 | 32
    heads of 128), sliding and global: the flash kernels take q
    ``bf16[32,16384,128]`` first and K, V ``bf16[4,16384,128]`` — what
    benchmark/readers/window_moe.py parses, and no repeated copy —; head norm
    and rope are one kernel each way (``head_norm_fwd`` | ``head_norm_bwd``,
    for q and for k); under the mixer's scope nothing broadcasts, copies or
    transposes 64 MB or more and no f32 array of 256 MB is written, other
    than the forward kernel's logsumexp on 128 lanes and XLA's copy of it to
    take lane 0 (ROADMAP S6: a ``kernels`` item of all four flash cells).
    The bytes the top-level operations outside the kernels name (results +
    operands of every fusion, copy, broadcast, reduce and convert): the
    parent's module of the same case **8.90 GiB** sliding | **6.73** global
    (the 8-fold repeat and its transpose's sum, q whole in f32 both ways, its
    half heads written and concatenated, a head-dim-major copy for the
    norm's reduction), this one **5.53 | 5.49 GiB**, held under 5.8."""
    from byteps_tpu.models import window_moe as wm
    from byteps_tpu.ops import head_norm as hn

    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    cfg = wm.WindowMoEConfig(compute_dtype=jnp.bfloat16)  # the published widths
    s, scope = cfg.max_seq, wm.SCOPES[stack]
    assert (s, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (16384, 32, 4, 128)
    lp = {name: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
          for name, shape in wm.stacks(cfg)[stack][1].items()}
    x = jax.ShapeDtypeStruct((1, s, cfg.d_model), jnp.bfloat16, sharding=one_chip)

    def loss(x, lp):
        return jnp.sum(wm._attention_mixer(cfg, x, lp, stack).astype(jnp.float32) ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 1)), x, lp).as_text()
    forward, backward = ((fa.FWD_WIN_KERNEL, fa.BWD_WIN_KERNEL) if stack == "win" else
                         (fa.FWD_KERNEL, fa.BWD_KERNEL))
    for kernel in (forward, backward):
        assert _kernel_operands(text, kernel)[:3] == [
            "bf16[32,16384,128]", "bf16[4,16384,128]", "bf16[4,16384,128]"]
    ops = _top_level(text)
    assert sum(o[4] and o[0].startswith(hn.FWD_KERNEL) for o in ops) == 2
    assert sum(o[4] and o[0].startswith(hn.BWD_KERNEL) for o in ops) == 2
    # under the scope a call's instruction starts with its kernel's name, which
    # is how benchmark/readers/window_moe.py's ``_flash_call`` tells the calls
    lse = {o[0] for o in ops if o[4] and o[0].startswith(forward)}
    assert len(lse) == 1 and sum(o[4] and o[0].startswith(backward) for o in ops) == 1
    for name, opcode, result, _, kernel, op_name in ops:
        size = _bytes_of(result)
        moved = opcode in ("broadcast", "copy", "transpose") and size >= 64 * 2**20
        wide = "f32[" in result and size >= 256 * 2**20
        if not kernel and scope in op_name and (moved or wide):
            assert lse & set(_sources(ops, name)), f"{name}: {opcode} of {result} under {scope}"
    parent, ceiling = _MIXER_BYTES[stack]
    assert _named_bytes(text) < ceiling * 2**30 < parent * 2**30


def test_early_routed_window_mixer_turns_each_head_once_at_published_widths(
        one_chip, no_compile_cache, monkeypatch):
    """``early_route_moe._mixer_part``'s gradient (no recomputation) for 2 x
    16 384 tokens at SmallThinker's widths (2560 → 28 | 4 heads of 128), a
    sliding layer: rope over the whole head is one kernel each way for q and
    for k (``head_rope_fwd`` | ``head_rope_bwd``, ``ops/head_norm.head_rope``)
    where ``moe_family.rope_partial`` was XLA's.  The q | k products stand
    token-major, heads side by side (``bf16[2,16384,3584]`` | ``…,512]``: what
    the forward kernel reads and the backward one writes), the banded flash
    kernels take q ``bf16[56,16384,128]`` and K, V ``bf16[8,16384,128]``
    straight from the pass; under ``window_attention`` nothing concatenates,
    slices or broadcasts 64 MB or more (``rope_partial``'s two half heads and
    their concatenation, both ways), no f32 array of 256 MB is written (its
    f32 copy of q) other than the forward kernel's logsumexp on 128 lanes and
    XLA's copy of it to take lane 0, and the one copy of 64 MB is dO's, which
    the output projection's transpose writes sequence-minor (the parent's
    too; ISSUE 56, the rule-seven check trinity's mixer has above).  The
    bytes the top-level operations outside the kernels name: **13.94 GiB**
    with ``rope_partial`` in ``head_rope``'s place (same tree, same case),
    **8.01** now, held under 8.5."""
    from byteps_tpu.models import early_route_moe as er
    from byteps_tpu.ops import head_norm as hn

    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    cfg = er.EarlyRouteMoEConfig(compute_dtype=jnp.bfloat16)  # the published widths
    b, s, scope = 2, cfg.max_seq, er.SCOPES["win"]
    assert (s, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (16384, 28, 4, 128)
    lp = {name: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
          for name, shape in er.stacks(cfg)["win"][1].items()}
    x = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16, sharding=one_chip)

    def loss(x, lp):
        return jnp.sum(er._mixer_part(cfg, x, lp, "win").x.astype(jnp.float32) ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 1)), x, lp).as_text()
    for kernel in (fa.FWD_WIN_KERNEL, fa.BWD_WIN_KERNEL):
        assert _kernel_operands(text, kernel)[:3] == [
            "bf16[56,16384,128]", "bf16[8,16384,128]", "bf16[8,16384,128]"]
    ops = _top_level(text)
    types = {o[0]: re.sub(r"\{.*", "", o[2].strip()) for o in ops}
    calls = lambda kernel: [o for o in ops if o[4] and o[0].startswith(kernel)]  # noqa: E731
    wide = ["bf16[2,16384,3584]", "bf16[2,16384,512]"]  # q's and k's heads side by side
    assert sorted(types[o[3][0]] for o in calls(hn.ROPE_FWD_KERNEL)) == wide
    assert sorted(types[o[0]] for o in calls(hn.ROPE_BWD_KERNEL)) == wide
    lse = {o[0] for o in calls(fa.FWD_WIN_KERNEL)}
    assert len(lse) == 1 and len(calls(fa.BWD_WIN_KERNEL)) == 1
    assert "rope_partial" not in text
    for name, opcode, result, _, kernel, op_name in ops:
        size = _bytes_of(result)
        if kernel or scope not in op_name or lse & set(_sources(ops, name)):
            continue
        moved = size >= 64 * 2**20 and (
            opcode in ("broadcast", "transpose", "concatenate", "slice")
            or re.search(r"/(concatenate|slice|split)$", op_name)
            or opcode == "copy" and not op_name.endswith("bhsk,hkd->bsd/transpose"))
        assert not moved, f"{name}: {opcode} of {result} under {scope}"
        assert not ("f32[" in result and size >= 256 * 2**20), f"{name}: {result} under {scope}"
    assert _named_bytes(text) < 8.5 * 2**30 < 13.94 * 2**30


# module → (GiB the parent's module names outside its kernels, GiB this one may)
_LATENT_MIXER_BYTES = {"forward": (4.05, 2.7), "gradient": (9.99, 7.5)}


def test_latent_attention_mixer_moves_each_tensor_once_at_published_widths(
        one_chip, no_compile_cache, monkeypatch):
    """``latent_moe._attention`` for 2 x 8192 tokens at JoyAI-LLM-Flash's
    widths (2048 → 1536 | 512 + 64 → 32 heads of 128 + 64 | 128), the forward
    alone (what remat runs a second time) and the gradient (no
    recomputation): the flash kernels take q, k ``bf16[64,8192,192]`` and v
    ``bf16[64,8192,128]`` — what benchmark/readers/latent_moe.py parses —;
    from the four token-major products to those operands and back is one
    kernel each way (``mla_heads_fwd`` | ``mla_heads_bwd``); under
    ``mla_attention`` nothing copies, transposes, broadcasts, concatenates or
    slices 64 MB or more (dO too is written head-major by its product:
    ``ops/mla_heads.merge_heads``) and no f32 array of 256 MB is written,
    other than the forward kernel's logsumexp on 128 lanes and XLA's copy of
    it to take lane 0 (ROADMAP S6f).  The bytes the top-level operations
    outside the kernels name (``_named_bytes``): the parent's module of the
    same case **4.05 GiB** forward | **9.99** gradient (both products written
    sequence-minor and five whole-tensor copies between layouts, q and k
    concatenated from halves, the rotary key broadcast to 32 heads, kv and the
    cotangents sliced, the rotary columns in f32 on a last dimension of 2),
    this one **1.28 | 5.30 GiB** (0.75 of the gradient's is the logsumexp's
    copy and squeeze), held under 2.7 | 7.5.  (ISSUE 50 counted the parent's
    module on a copy of its own at 4.36 | 10.86.)"""
    from byteps_tpu.models import latent_moe as lm
    from byteps_tpu.ops import mla_heads as mh

    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    cfg = lm.LatentMoEConfig(compute_dtype=jnp.bfloat16)  # the published widths
    b, s, scope = 2, cfg.max_seq, "mla_attention"
    assert (s, cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim) == (
        8192, 32, 128, 64, 128)
    lp = {name: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
          for name, shape in lm._attention_shapes(cfg).items()}
    x = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16, sharding=one_chip)

    def loss(x, lp):
        return jnp.sum(lm._attention(cfg, x, lp).astype(jnp.float32) ** 2)

    for module, fn in (("forward", loss), ("gradient", jax.grad(loss, argnums=(0, 1)))):
        text = _compile(fn, x, lp).as_text()
        ops = _top_level(text)
        calls = lambda kernel: [o[0] for o in ops if o[4] and o[0].startswith(kernel)]  # noqa: E731
        backward = module == "gradient"
        wanted = {fa.FWD_KERNEL: 1, mh.FWD_KERNEL: 1, fa.BWD_KERNEL: backward,
                  mh.BWD_KERNEL: backward}
        assert {k: len(calls(k)) for k in wanted} == wanted
        for kernel in (fa.FWD_KERNEL, fa.BWD_KERNEL)[:1 + backward]:
            assert _kernel_operands(text, kernel)[:3] == [
                "bf16[64,8192,192]", "bf16[64,8192,192]", "bf16[64,8192,128]"]
        lse = set(calls(fa.FWD_KERNEL))
        for name, opcode, result, _, kernel, op_name in ops:
            size = _bytes_of(result)
            moved = size >= 64 * 2**20 and (
                opcode in ("broadcast", "copy", "transpose", "concatenate", "slice")
                or re.search(r"/(concatenate|slice|split|broadcast_in_dim)$", op_name))
            wide = "f32[" in result and size >= 256 * 2**20
            if not kernel and scope in op_name and (moved or wide):
                assert lse & set(_sources(ops, name)), f"{name}: {opcode} of {result} under {scope}"
        parent, ceiling = _LATENT_MIXER_BYTES[module]
        assert _named_bytes(text) < ceiling * 2**30 < parent * 2**30


def test_looped_dense_step_compiles_at_published_widths(one_chip, no_compile_cache, monkeypatch):
    """Ouro-2.6B's widths, 6 layers run 4 times over 1 x 8192 tokens, adamw,
    bf16 operands: the whole train step of ``build_train_step`` — the scan
    over the loop steps around the scan over the layers, the rotary passes
    and the flash kernels inside both, the four heads as one blocked loss, the
    exit gate — for one described chip.  What it keeps for the backward pass
    stays what the family's docstring says: a (loop step, layer) the layer's
    f32 input and the flash kernel's output and row statistics, the layer
    rebuilt whole (with the input of the MLP part kept too the temporaries
    are 12.75 GiB), so they stay under 10.7 GiB by the compiler's count
    (10.45)."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from byteps_tpu.models import looped_dense as ld
    from byteps_tpu.models.transformer import build_train_step
    from byteps_tpu.parallel.mesh_utils import make_training_mesh

    hn = importlib.import_module("byteps_tpu.ops.head_norm")
    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    cfg = ld.LoopedDenseConfig(n_layers=6, compute_dtype=jnp.bfloat16)
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff, cfg.n_loops, cfg.max_seq) == (
        2048, 16, 128, 5632, 4, 8192)
    mesh = make_training_mesh(1, {"dp": 1, "pp": 1, "sp": 1, "tp": 1},
                              devices=[one_chip._device])
    held = NamedSharding(mesh, P())
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=held)
              for k, (s, _, _) in cfg.layouts().items()}
    tokens = jax.ShapeDtypeStruct((1, cfg.max_seq), jnp.int32,
                                  sharding=NamedSharding(mesh, P("dp", "sp")))
    tx = optax.adamw(1e-6)
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=held),
                         jax.eval_shape(tx.init, params))
    compiled = build_train_step(cfg, mesh, tx).lower(params, state, tokens, tokens).compile()
    text = compiled.as_text()
    for kernel in (fa.FWD_KERNEL, fa.BWD_KERNEL, "head_rope_fwd", "head_rope_bwd"):
        assert kernel in text, f"{kernel} is not in the compiled program"
    memory = compiled.memory_analysis()
    held_bytes = 12 * sum(math.prod(s) for s, _, _ in cfg.layouts().values())
    # parameters and adamw's two moments (+ its step count, the tokens, padding)
    assert held_bytes <= memory.argument_size_in_bytes < held_bytes + 2**20
    assert memory.temp_size_in_bytes < 10.7 * 2**30


@pytest.mark.parametrize("policy, calls", [("family", (1, 1)), ("none", (2, 1))])
def test_state_space_layer_runs_the_scan_once_at_published_widths(
        one_chip, no_compile_cache, monkeypatch, policy, calls):
    """The gradient of a rebuilt Mamba-2 layer (``_ssm_layer`` under
    ``moe_family.walk``'s ``jax.checkpoint``) for 2 x 8192 tokens at the
    published widths: the compiled module calls ``ssd_scan_fwd`` and
    ``ssd_scan_bwd`` once each — the recomputation keeps the entering states
    and y by name (``ops/ssd.SAVED``: what ``ssm_moe._hidden`` tells ``walk``
    the ``ssm`` stack keeps) —, where a ``jax.checkpoint`` with no policy,
    what the family had, calls the forward kernel twice.  The kernels take x,
    B and C token-major: no ``transpose`` and no layout-changing ``copy`` of
    4 MB or more under ``ssd_scan`` but the per-token scalars' (B or C alone
    is 33.5 MB in bf16), and the kept arrays are f32."""
    from byteps_tpu.models import moe_family as mf
    from byteps_tpu.models import ssm_moe as sm
    from byteps_tpu.ops import ssd
    from byteps_tpu.ops import ssd_kernels as sk

    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    cfg = sm.SsmMoEConfig(compute_dtype=jnp.bfloat16, layer_types=("M",))
    shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)
    params = {f"ssm.{k}": shape(1, *s) for k, s in sm.stacks(cfg)["ssm"][1].items()}
    kept = {"ssm": ssd.SAVED} if policy == "family" else {}

    def loss(x, params):
        run = {"ssm": lambda x, lp: sm._ssm_layer(cfg, x, lp)}
        return jnp.sum(mf.walk(cfg, run, kept, params, x)[0].astype(jnp.float32) ** 2)

    compiled = _compile(jax.grad(loss, argnums=(0, 1)),
                        shape(2, 8192, 2688, dtype=jnp.bfloat16), params)
    text = compiled.as_text()
    kernels = [op_name for *_, kernel, op_name in _top_level(text) if kernel]
    assert tuple(sum(bool(re.search(rf"\b{name}\b", op_name)) for op_name in kernels)
                 for name in (sk.FWD_KERNEL, sk.BWD_KERNEL)) == calls
    # dt and the log-decay, and their cotangents, are turned to a row a head
    # for the kernels (4 MB each: XLA's); nothing else is
    assert [found for found in _relayouts_under(text, "ssd_scan", 4 * 2**20)
            if "f32[2,64,8192]" not in found] == []
    assert _kernel_operands(text, sk.BWD_KERNEL)[5:] == [
        "f32[16,64,128,512]", "f32[2,8192,4096]"]  # the entering states and dy
    # the kept arrays, 268 MB each, beside what the layer's gradient took before
    assert compiled.memory_analysis().temp_size_in_bytes < 3.0 * 2**30


@pytest.mark.parametrize("implementation", ["kernels", "xla"])
def test_chunked_delta_rule_compiles_at_published_widths(one_chip, no_compile_cache, monkeypatch,
                                                         implementation):
    """One sequence of 16 384 tokens, 16 key and 32 value heads of 128
    token-major (heads side by side along the lanes, as every caller has
    them), chunks of 64, bf16 operands: the rule and its five gradients, with
    what the backward pass keeps well under what a state a token would take (34
    GB).  ``kernels`` is the path a TPU takes at these shapes — the lowered
    module holds the three Pallas kernels, forward and backward —, ``xla``
    the chunked form that stays their oracle (tools/gdn_tune.py times it)."""
    from byteps_tpu.ops import gated_delta as gd
    from byteps_tpu.ops import gated_delta_kernels as gk

    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)
    s = 16384
    assert gd._kernel_path(gd.CHUNK, 128, 128, interpret=False)
    rule = (functools.partial(gd.chunked_gated_delta_rule, compute_dtype=jnp.bfloat16)
            if implementation == "kernels"
            else lambda *a: gd._chunked_xla(*a, gd.CHUNK, jnp.bfloat16))

    def loss(q, k, v, g, beta):
        return jnp.sum(rule(q, k, v, g, beta))

    args = (shape(1, s, 16, 128), shape(1, s, 16, 128), shape(1, s, 32, 128),
            shape(1, s, 32, dtype=jnp.float32), shape(1, s, 32, dtype=jnp.float32))
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), *args)
    text = compiled.as_text()
    if implementation == "kernels":
        for kernel in (gk.INVERSE_KERNEL, gk.FWD_KERNEL, gk.BWD_KERNEL):
            assert kernel in text, f"{kernel} is not in the compiled program"
        assert text.count("tpu_custom_call") >= 3
        assert "while" not in text  # no scan over the 256 chunks is left to XLA
        # the forward alone: the inverse and the walk, no residual written
        forward = _compile(loss, *args).as_text()
        assert gk.FWD_KERNEL in forward and gk.BWD_KERNEL not in forward
    else:
        assert "while" in text and "tpu_custom_call" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * 2**30


@pytest.mark.parametrize("policy, calls", [("family", (1, 1, 1)), ("none", (2, 2, 1))])
def test_delta_layer_runs_the_inverse_once_at_published_widths(
        one_chip, no_compile_cache, monkeypatch, policy, calls):
    """The gradient of a rebuilt linear layer (``x + _delta_mixer``, the
    part ``delta_moe._layer_parts`` hands ``_hidden``'s period) for one
    sequence of 16 384 tokens at Qwen3-Next's widths: the compiled module
    calls ``gdn_chunk_inverse``, ``gdn_scan_fwd`` and ``gdn_scan_bwd`` once
    each — the recomputation keeps T, the entering states and o by name
    (``gated_delta_kernels.SAVED``) —, where a ``jax.checkpoint`` with no
    policy, what the family had, calls both forward kernels twice.  The
    operands stay token-major either way: no ``transpose`` and no
    layout-changing ``copy`` of 64 MB or more under ``gdn_scan``."""
    from byteps_tpu.models import delta_moe as dm
    from byteps_tpu.ops import gated_delta_kernels as gk

    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    cfg = dm.DeltaMoEConfig(compute_dtype=jnp.bfloat16)  # the published widths
    assert cfg.remat and cfg.max_seq == 16384
    lp = {name: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
          for name, shape in dm.layer_shapes(cfg)["lin"].items()}
    x = jax.ShapeDtypeStruct((1, cfg.max_seq, cfg.d_model), jnp.bfloat16, sharding=one_chip)
    layer = dm._layer_parts(cfg)[0] if policy == "family" else jax.checkpoint(
        dm._layer_parts(dataclasses.replace(cfg, remat=False))[0])

    def loss(x, lp):
        return jnp.sum(layer(x, lp).astype(jnp.float32) ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 1)), x, lp).as_text()
    kernels = [op_name for *_, kernel, op_name in _top_level(text) if kernel]
    assert tuple(sum(bool(re.search(rf"\b{name}\b", op_name)) for op_name in kernels)
                 for name in (gk.INVERSE_KERNEL, gk.FWD_KERNEL, gk.BWD_KERNEL)) == calls
    assert _relayouts_under(text, "gdn_scan", 64 * 2**20) == []


@pytest.mark.parametrize("program", [
    pytest.param("the_cells_step", marks=pytest.mark.slow), "a_short_periods_mixers"])
def test_delta_step_copies_nothing_it_keeps_at_published_widths(one_chip, no_compile_cache,
                                                                monkeypatch, program):
    """The whole train step of ``build_train_step`` for one period of
    Qwen3-Next at the published widths — three gated-delta layers and a gated
    attention layer over 1 x 16 384 tokens, 16 of 512 experts held, adamw, bf16
    operands: the cell's program — for one described chip.  A period's linear
    layers are unrolled (``delta_moe._hidden``), so what a layer keeps for its
    backward pass (x 64 MiB; T, the entering states and o 256 MiB each) is
    written once where it is made and read where it is: the module holds no
    ``dynamic-update-slice`` and no ``dynamic-slice`` of 64 MB or more (as
    the body of a ``lax.scan`` each kept array was copied into the scan's
    stack and out again: PERF.md §6, PRs 58 and 60; the blocked loss's 8 MiB
    blocks are the largest left), every kernel of the rule is called once a
    layer, and the program fits the chip beside nothing else of its size.

    The step takes ≈ 70 s to compile alone (206 s among six workers), so
    tier-1 holds its twin: the gradient of ``_hidden`` itself — the same
    period function, the same widths and what each layer keeps — over the
    shortest period in which a scan put back would have two turns to stack
    (two linear layers and the attention layer), the MLP parts, the loss and
    the optimizer left out."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from byteps_tpu.models import delta_moe as dm
    from byteps_tpu.models.transformer import build_train_step
    from byteps_tpu.ops import gated_delta_kernels as gk
    from byteps_tpu.parallel.mesh_utils import make_training_mesh

    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    whole = program == "the_cells_step"
    cfg = dm.DeltaMoEConfig(vocab_size=18992, experts_held=16, compute_dtype=jnp.bfloat16,
                            **(dict(n_layers=4) if whole else
                               dict(n_layers=3, full_attention_interval=3)))  # widths as published
    assert (cfg.n_periods, cfg.max_seq, cfg.remat) == (1, 16384, True)
    mesh = make_training_mesh(1, {"dp": 1, "pp": 1, "sp": 1, "tp": 1},
                              devices=[one_chip._device])
    held = NamedSharding(mesh, P())
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=held)
              for k, (s, _, _) in cfg.layouts().items()}
    tokens = jax.ShapeDtypeStruct((1, cfg.max_seq), jnp.int32,
                                  sharding=NamedSharding(mesh, P("dp", "sp")))
    if whole:
        tx = optax.adamw(1e-6)
        state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=held),
                             jax.eval_shape(tx.init, params))
        compiled = build_train_step(cfg, mesh, tx).lower(params, state, tokens, tokens).compile()
    else:
        parts = dm._layer_parts
        monkeypatch.setattr(dm, "_layer_parts", lambda cfg: parts(cfg)[:2] + (
            lambda x, lp: (x, jnp.zeros((len(dm.ROUTING_STATS),), jnp.int32)),))
        compiled = _compile(jax.grad(lambda p, t: jnp.sum(
            dm._hidden(cfg, p, t)[0].astype(jnp.float32) ** 2)), params, tokens)
    text = compiled.as_text()
    assert _slices_moved(text, 64 * 10**6) == []
    kernels = [op_name for *_, kernel, op_name in _top_level(text) if kernel]
    layers = cfg.full_attention_interval - 1
    assert tuple(sum(bool(re.search(rf"\b{name}\b", op_name)) for op_name in kernels)
                 for name in (gk.INVERSE_KERNEL, gk.FWD_KERNEL, gk.BWD_KERNEL)) == (layers,) * 3
    if whole:
        memory = compiled.memory_analysis()
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 10.2 * 2**30


def test_selective_scan_keeps_its_state_out_of_the_hbm_a_token(one_chip, no_compile_cache,
                                                               monkeypatch):
    """The Mamba-1 scan at the cell's shape, XLA's form compiled for a
    described v5e: three token loops a gradient (forward, a chunk's rebuild,
    the adjoint), and no array with a state a TOKEN — the largest f32 array
    is a chunk's worth of states, (128, 1, 16, 5120)."""
    from byteps_tpu.ops import selective_scan as ss

    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    tokens, channels, state = 16384, 5120, 16
    x = jax.ShapeDtypeStruct((1, tokens, channels), jnp.bfloat16, sharding=one_chip)
    dt = jax.ShapeDtypeStruct((1, tokens, channels), jnp.float32, sharding=one_chip)
    a = jax.ShapeDtypeStruct((channels, state), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((1, tokens, state), jnp.bfloat16, sharding=one_chip)
    d = jax.ShapeDtypeStruct((channels,), jnp.float32, sharding=one_chip)

    def loss(x, dt, a, b, c, d):
        return jnp.sum(ss.selective_scan(x, dt, a, b, c, d).astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=tuple(range(6))), x, dt, a, b, b, d)
    text = compiled.as_text()
    sizes = [int(n) * int(m) * state * channels
             for n, m in re.findall(r"f32\[(\d+),(\d+),1,16,5120\]", text)]
    assert sizes and max(sizes) <= 128 * state * channels * 128  # a state a chunk, all chunks
    assert not re.search(r"f32\[16384,1,16,5120\]|f32\[1,16384,16,5120\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


@pytest.mark.parametrize("implementation", ["kernels", "xla"])
def test_channel_delta_rule_compiles_at_published_widths(one_chip, no_compile_cache, monkeypatch,
                                                         implementation):
    """Kimi Delta Attention's rule for one sequence of 16 384 tokens, 32 heads
    of 128 | 128 token-major, a log-decay a key channel in f32, chunks of 64 in
    sub-blocks of 16, bf16 operands: the rule and its five gradients.
    ``kernels`` is the path a TPU takes at these shapes — the compiled module
    holds the three Pallas kernels of ops/kda_kernels.py, no scan over the
    chunks is left to XLA, and what the backward pass keeps (T, the entering
    states, g and the cotangents around them) stays under 2 GiB.  ``xla`` is the
    chunked form that stays their oracle, a block of ``HEAD_BLOCK`` heads at a
    time: all 32 heads at once, its backward pass kept 4.57 GiB of temporaries
    (a dozen f32 arrays of q's size; PERF.md §6 PR 68) and the step 17.8 GiB of
    the chip's 15.75; by blocks of 8 it reads 2.4 GiB and is held under 3 here.
    Nothing of a chunk's sub × sub × d_k terms stands for a whole sequence (4.3
    GB at f32) in either: the largest f32 array is q's size."""
    from byteps_tpu.ops import gated_delta as gd
    from byteps_tpu.ops import kda_kernels as kk

    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)
    s, h, d = 16384, 32, 128
    rule = (functools.partial(gd.chunked_gated_delta_rule, compute_dtype=jnp.bfloat16)
            if implementation == "kernels"
            else lambda *a: gd._by_head_blocks(*a, gd.CHUNK, gd.SUB_CHUNK, jnp.bfloat16))

    def loss(q, k, v, g, beta):
        return jnp.sum(rule(q, k, v, g, beta))

    args = (shape(1, s, h, d), shape(1, s, h, d), shape(1, s, h, d),
            shape(1, s, h, d, dtype=jnp.float32), shape(1, s, h, dtype=jnp.float32))
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), *args)
    text = compiled.as_text()
    if implementation == "kernels":
        for kernel in (kk.INVERSE_KERNEL, kk.FWD_KERNEL, kk.BWD_KERNEL):
            assert kernel in text, f"{kernel} is not in the compiled program"
        assert text.count("tpu_custom_call") == 3 and "while" not in text
        forward = _compile(loss, *args).as_text()
        assert kk.FWD_KERNEL in forward and kk.BWD_KERNEL not in forward
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30
    else:
        assert "while" in text and "tpu_custom_call" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2**30
    widest = max(_bytes_of(m.group(0)) for m in re.finditer(r"f32\[[\d,]*\]", text))
    assert widest <= 4 * s * h * d


def test_position_free_latent_layer_takes_the_kernels_at_published_widths(
        one_chip, no_compile_cache, monkeypatch):
    """``moe_family.latent_attention`` as the channel-delta family calls it —
    no query bottleneck, ``theta`` None — for 1 x 16 384 tokens at Kimi
    Linear's widths (2304 → 32 heads of 128 + 64 | 128 through 512 + 64): the
    gradient holds one call of each of the four kernels JoyAI's layer has, the
    flash pair at q, k ``bf16[32,16384,192]`` and v ``bf16[32,16384,128]`` —
    what benchmark/readers/channel_delta_moe.py parses — and the pass of
    ``ops/mla_heads.py`` each way, its tables cos 1 and sin 0."""
    from byteps_tpu.models import channel_delta_moe as cd
    from byteps_tpu.models import moe_family as mf
    from byteps_tpu.ops import mla_heads as mh

    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    cfg = cd.ChannelDeltaMoEConfig(compute_dtype=jnp.bfloat16)  # the published widths
    assert (cfg.max_seq, cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
            cfg.rope_theta) == (16384, 32, 128, 64, 128, None)
    lp = {name: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
          for name, shape in cd.stacks(cfg)["latent"][1].items()}
    x = jax.ShapeDtypeStruct((1, cfg.max_seq, cfg.d_model), jnp.bfloat16, sharding=one_chip)

    def loss(x, lp):
        y = mf.latent_attention(cfg, x, lp, "nope_latent_attention", cfg.rope_theta)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 1)), x, lp).as_text()
    ops = _top_level(text)
    for kernel in (fa.FWD_KERNEL, fa.BWD_KERNEL, mh.FWD_KERNEL, mh.BWD_KERNEL):
        assert len([o for o in ops if o[4] and o[0].startswith(kernel)]) == 1, kernel
    for kernel in (fa.FWD_KERNEL, fa.BWD_KERNEL):
        assert _kernel_operands(text, kernel)[:3] == [
            "bf16[32,16384,192]", "bf16[32,16384,192]", "bf16[32,16384,128]"]
