"""The main path's kernels compiled for the chip at their real widths,
without a chip: the TPU's compiler is installed here and compiles for a
described v5e (guides/on-chip-measurement §2).  Nothing runs, so this says
nothing of results or times — it catches what Mosaic or XLA:TPU would refuse
(a block over the VMEM limit, a misaligned slice) before chip time is spent.

The topology is described inside a fixture, never at import: only one
process may load libtpu, and every xdist worker imports every test file.
All such tests stay in THIS file, so one worker holds the library.
"""

import dataclasses
import functools
import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from byteps_tpu.ops import _dispatch

fa = importlib.import_module("byteps_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).compile()


def test_flash_kernels_compile_at_the_latent_attention_shape(one_chip, no_compile_cache,
                                                             monkeypatch):
    """(2, 32, 8192, 192 | 128) causal, the forward kernel and the backward
    kernel (dQ's f32 accumulator over the whole sequence, 8.4 MB in VMEM
    under the limit the kernel states), with the blocks ops/flash_blocks.json
    commits for that sequence."""
    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    b, h, s, d_qk, d_v = 2, 32, 8192, 192, 128
    q, k = (jax.ShapeDtypeStruct((b, h, s, d_qk), jnp.bfloat16, sharding=one_chip)
            for _ in range(2))
    v = jax.ShapeDtypeStruct((b, h, s, d_v), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, scale=d_qk ** -0.5)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, v).as_text()
    for kernel in (fa.FWD_KERNEL, fa.BWD_KERNEL):
        assert kernel in text, f"{kernel} is not in the compiled program"
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("t, d, experts, k, first_rows, temp_gib", [
    (16384, 2048, 256, 8, 4608, 0.6), (32768, 2560, 64, 6, 27648, 1.5)],
    ids=["joyai_llm_flash_ep32", "smallthinker_21b_ep8"])
def test_held_expert_layer_compiles_at_published_widths(one_chip, no_compile_cache,
                                                        t, d, experts, k, first_rows, temp_gib):
    """Top-k of ``experts``, 8 held experts of width 768, at two cells'
    shapes — 16 384 tokens at d 2048, top-8 of 256, and the heaviest load the
    benchmark has, 32 768 tokens at d 2560, top-6 of 64: the sort, the grouped
    products (XLA:TPU's own ragged-dot kernel) and their gradients, the first
    chunk and the tail's loop."""
    from byteps_tpu.parallel import moe

    f, held = 768, 8
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)

    def loss(g, router, bias, w_gate, w_up, w_down):
        ids, weights = moe.sigmoid_topk_route(g, router, bias, k, 2.5)
        y, stats = moe.held_expert_mlp(g, ids, weights, w_gate, w_up, w_down,
                                       lo=0, n_experts=experts)
        return jnp.sum(y), stats

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 3, 4, 5), has_aux=True),
        shape(t, d), shape(d, experts, dtype=jnp.float32), shape(experts, dtype=jnp.float32),
        shape(held, d, f), shape(held, d, f), shape(held, f, d))
    assert "ragged-dot" in compiled.as_text()
    # d and 768 are whole tiles of the grouped products' kernel: passed as they are
    assert (moe.held_tiles(d), moe.held_tiles(f)) == (d, f)
    assert not re.search(rf" pad\(\S*\[{held},({d},{f}|{f},{d})\]", compiled.as_text())
    assert moe.held_walk(t * k, held, experts)[0] == first_rows
    # a chunk of 9/8 of the even load at a time (a tail chunk is a quarter of
    # it): 0.44 | 1.17 GiB, far under what all t·k slots would take
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gib * 2**30


def test_flash_kernels_compile_at_the_gated_attention_shape(one_chip, no_compile_cache,
                                                            monkeypatch):
    """(1, 16, 16384, 256 | 256) causal, the forward kernel and the backward
    kernel, with the blocks ops/flash_blocks.json commits for that sequence:
    1024 x 1024, which the pair of backward kernels could not hold under
    Mosaic's default VMEM limit at head size 256; the one kernel asks for its
    own (dQ's accumulator alone is 16.8 MB here)."""
    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    assert fa.tuned_blocks(16384) == (1024, 1024)
    q = jax.ShapeDtypeStruct((1, 16, 16384, 256), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True, scale=1 / 16).astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, q).as_text()
    for kernel in (fa.FWD_KERNEL, fa.BWD_KERNEL):
        assert kernel in text, f"{kernel} is not in the compiled program"


def test_flash_kernels_compile_at_the_grouped_query_shape(one_chip, no_compile_cache, monkeypatch):
    """(2, 32, 8192, 64 | 64) causal — LFM2's attention layer with its 8
    key/value heads repeated, half a lane tile in the contraction — the
    forward kernel and the backward kernel, with the blocks
    ops/flash_blocks.json commits for that sequence."""
    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    q = jax.ShapeDtypeStruct((2, 32, 8192, 64), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True, scale=1 / 8).astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, q).as_text()
    for kernel in (fa.FWD_KERNEL, fa.BWD_KERNEL):
        assert kernel in text, f"{kernel} is not in the compiled program"
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("window,kv_heads", [(2048, 32), (None, 32), (2048, 4), (None, 4)],
                         ids=["banded", "global", "banded_grouped", "global_grouped"])
def test_flash_kernels_compile_at_the_sliding_window_shape(one_chip, no_compile_cache,
                                                           monkeypatch, window, kv_heads):
    """(1, 32 | 32 or 4, 16384, 128 | 128) — Trinity-Mini's mixers, with the 4
    key/value heads repeated to 32 and as they are (the kernels find head
    ``h // 8`` by index map): the banded pair at window 2048 with the blocks
    ops/flash_blocks.json commits for (16384, 2048), an innermost grid axis
    as long as the band is wide and not as the sequence; and the full causal
    pair of the global layer at the sequence's plain entry.  Grouped, K and V
    are the kernels' operands at 4 heads and no array of 32 stands for them."""
    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    bq, bk = fa.tuned_blocks(16384, window)
    assert (16384, 2048) in fa._tuned_table()["banded"]
    assert max(fa._band_steps(16384, bq, bk, 2048)) <= 6 < 16384 // max(bq, bk)
    q = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, kv_heads, 16384, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, scale=128 ** -0.5, window=window)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, k).as_text()
    wanted = (fa.FWD_WIN_KERNEL, fa.BWD_WIN_KERNEL) if window else (fa.FWD_KERNEL, fa.BWD_KERNEL)
    for kernel in wanted:
        assert kernel in text, f"{kernel} is not in the compiled program"
        q_, k_, v_ = _kernel_operands(text, kernel)[:3]
        assert (q_, k_, v_) == ("bf16[32,16384,128]",) + (f"bf16[{kv_heads},16384,128]",) * 2
    assert (fa.FWD_WIN_KERNEL in text) == bool(window)
    assert text.count("tpu_custom_call") >= 2
    if kv_heads < 32:
        assert not re.search(r"bf16\[4,8,16384,128\]\S* broadcast\(", text)


@pytest.mark.parametrize("queries", [16384, 8192], ids=["both_copies", "noisy_copy_alone"])
def test_flash_kernels_compile_at_the_block_diffusion_shape(one_chip, no_compile_cache,
                                                            monkeypatch, queries):
    """(1, 32 | 4, 16384 or 8192 | 16384, 128 | 128) — SDAR's mixer under the
    block-diffusion mask at block length 4: the pair ``flash_fwd_bd`` |
    ``flash_bwd_bd`` with its tables of tiles as scalars before the grid, at
    the blocks the artifact commits (dQ's f32 accumulator over the queries, 8
    MiB in VMEM at 16384); both copies' queries, and the noised copy's alone
    (a last layer's call).  K and V are the kernels' operands at 4 heads; the
    innermost grid axes are as long as the table's longest rows, not as the
    sequence, and the mask keeps under 0.6 of a causal call's tile pairs."""
    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    bq, bk = fa.tuned_block_diffusion_blocks(16384, 4, queries)
    tiles = fa._bd_tiles(queries, 8192, 4, bq, bk)
    causal_pairs = sum(min((qi + 1) * bq - 1, 16383) // bk + 1 for qi in range(16384 // bq))
    if queries == 16384:
        assert tiles["pairs"] < 0.6 * causal_pairs
    assert tiles["steps_f"] <= 8192 // bk + 8192 // bq and tiles["steps_b"] <= 2 * 8192 // bq
    q = jax.ShapeDtypeStruct((1, 32, queries, 128), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out, lse = fa.block_diffusion_attention_lse(q, k, v, 4, scale=128 ** -0.5)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, k).as_text()
    for kernel in (fa.FWD_BD_KERNEL, fa.BWD_BD_KERNEL):
        assert kernel in text, f"{kernel} is not in the compiled program"
        q_, k_, v_ = [o for o in _kernel_operands(text, kernel) if o.startswith("bf16")][:3]
        assert (q_, k_, v_) == (f"bf16[32,{queries},128]",) + ("bf16[4,16384,128]",) * 2
    assert text.count("tpu_custom_call") >= 2
    assert not re.search(r"bf16\[4,8,16384,128\]\S* broadcast\(", text)


@pytest.mark.parametrize("window", [4096, None], ids=["banded_group_of_7", "global_group_of_7"])
def test_flash_kernels_compile_at_the_early_routed_shape(one_chip, no_compile_cache, monkeypatch,
                                                         window):
    """(2, 28 | 4, 16384, 128 | 128) — SmallThinker's mixers: seven query
    heads a key/value head (the kernels find head ``h // 7`` by index map; K
    and V are their operands at 2 x 4 heads and no array of 56 stands for
    them), the banded pair at window 4096 with the blocks
    ops/flash_blocks.json commits for (16384, 4096), and the full causal pair
    of the global layer."""
    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    assert (16384, 4096) in fa._tuned_table()["banded"]
    q = jax.ShapeDtypeStruct((2, 28, 16384, 128), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((2, 4, 16384, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, scale=128 ** -0.5, window=window)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, k).as_text()
    wanted = (fa.FWD_WIN_KERNEL, fa.BWD_WIN_KERNEL) if window else (fa.FWD_KERNEL, fa.BWD_KERNEL)
    for kernel in wanted:
        assert kernel in text, f"{kernel} is not in the compiled program"
        q_, k_, v_ = _kernel_operands(text, kernel)[:3]
        assert (q_, k_, v_) == ("bf16[56,16384,128]",) + ("bf16[8,16384,128]",) * 2
    assert (fa.FWD_WIN_KERNEL in text) == bool(window)
    assert not re.search(r"bf16\[2,4,7,16384,128\]\S* broadcast\(", text)


def test_flash_kernels_compile_at_sixteen_query_heads_a_key_value_head(one_chip, no_compile_cache,
                                                                       monkeypatch):
    """(2, 32 | 2, 8192, 128 | 128) — the state-space family's attention
    layer: sixteen query heads a key/value head (the kernels find head
    ``h // 16`` by index map; K and V are their operands at 2 x 2 heads), the
    full causal pair with the blocks ops/flash_blocks.json commits for 8192,
    no positions."""
    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    q = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((2, 2, 8192, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, scale=128 ** -0.5)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, k).as_text()
    for kernel in (fa.FWD_KERNEL, fa.BWD_KERNEL):
        assert kernel in text, f"{kernel} is not in the compiled program"
        q_, k_, v_ = _kernel_operands(text, kernel)[:3]
        assert (q_, k_, v_) == ("bf16[64,8192,128]",) + ("bf16[4,8192,128]",) * 2
    assert not re.search(r"bf16\[2,2,16,8192,128\]\S* broadcast\(", text)


def test_flash_kernels_compile_at_sixteen_heads_each_with_its_own_keys(one_chip, no_compile_cache,
                                                                      monkeypatch):
    """(1, 16 | 16, 8192, 128 | 128) — the looped dense family's attention:
    the kernel's plainest shape, every query head its own key/value head,
    the full causal pair with the blocks ops/flash_blocks.json commits for
    8192."""
    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    q = jax.ShapeDtypeStruct((1, 16, 8192, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, scale=128 ** -0.5)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, q).as_text()
    for kernel in (fa.FWD_KERNEL, fa.BWD_KERNEL):
        assert kernel in text, f"{kernel} is not in the compiled program"
        assert _kernel_operands(text, kernel)[:3] == ["bf16[16,8192,128]"] * 3


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


def test_ungated_held_expert_layer_compiles_at_published_widths(one_chip, no_compile_cache):
    """16 384 tokens at d 2688, top-6 of 128, 8 held UNGATED experts 1856
    wide (two matrices each, relu squared between them): the sort, the grouped
    products and their gradients, the first chunk and the tail's loop."""
    from byteps_tpu.models.ssm_moe import relu2
    from byteps_tpu.parallel import moe

    t, d, f, held, experts, k = 16384, 2688, 1856, 8, 128, 6
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)

    def loss(g, router, bias, w_up, w_down):
        ids, weights = moe.sigmoid_topk_route(g, router, bias, k, 2.5)
        plan = moe.held_expert_plan(ids, 0, held)
        y, stats = moe.held_expert_apply(g, plan, weights, None, w_up, w_down, experts, relu2)
        return jnp.sum(y), stats

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 3, 4), has_aux=True),
        shape(t, d), shape(d, experts, dtype=jnp.float32), shape(experts, dtype=jnp.float32),
        shape(held, d, f), shape(held, f, d))
    assert compiled.as_text().count("ragged-dot") >= 2
    # neither width is a multiple of 256, and XLA:TPU tiles K and N by the
    # largest of 512 | 256 | 128 dividing them: as they come all 12 calls (the
    # first chunk's and the tail's, forward and the gradient forms) read
    # "512,128,128", 6615 grid steps a product at a tenth of the MXU's peak;
    # held_tiles pads both to whole tiles
    tilings = re.findall(r'ragged_dot_tiling="(\d+),(\d+),(\d+)"', compiled.as_text())
    assert len(tilings) == 12
    assert all("128" not in (tk, tn) for _, tk, tn in tilings), tilings
    assert moe.held_walk(t * k, held, experts)[0] == 7168  # 9/8 of 6144, in tiles of 512
    # a chunk of 7168 rows at a time: 1.54 GiB, far under what all 98 304
    # slots would take (0.91 at the published widths: the two padded matrices
    # and their two padded gradients, 96 MiB each, stand where the arguments and
    # the outputs themselves did, and eleven more buffers grow from 76 to 96)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6 * 2**30


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


def _relayouts_under(text: str, scope: str, least_bytes: int) -> list:
    """The ``transpose`` and ``copy`` instructions of an optimized module (any
    computation, fused ones too) whose result holds at least ``least_bytes``
    and whose ``op_name`` lies under ``scope``.  XLA:TPU writes a change of
    layout as a ``copy`` between two layouts; a reshape that is none is a
    ``bitcast`` and is not listed."""
    import math
    import re

    item = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* (transpose|copy)\(", line)
        if not m or scope not in (re.search(r'op_name="([^"]*)"', line) or [""])[0]:
            continue
        size = math.prod(int(d) for d in m.group(3).split(",") if d) * item.get(m.group(2), 4)
        if size >= least_bytes:
            found.append(f"{m.group(1)}: {m.group(4)} of {m.group(2)}[{m.group(3)}]")
    return found


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


_ITEM = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
_SHAPE = r"\b(bf16|f32|s32|u32|pred)\[([\d,]*)\]"


def _slices_moved(text: str, least_bytes: int) -> list:
    """The ``dynamic-slice`` and ``dynamic-update-slice`` instructions of an
    optimized module (any computation) that move at least ``least_bytes``: a
    slice's result, an update's written operand — what a ``lax.scan`` reads
    from and writes to its stack a turn."""
    defined = re.compile(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\((.*)$", re.M)

    def size(dtype, dims):
        return math.prod(int(d) for d in dims.split(",") if d) * _ITEM.get(dtype, 4)

    sizes = {name: size(dtype, dims) for name, dtype, dims, _, _ in defined.findall(text)}
    found = []
    for name, dtype, dims, op, operands in defined.findall(text):
        if op == "dynamic-slice":
            moved = size(dtype, dims)
        elif op == "dynamic-update-slice":
            moved = sizes[re.findall(r"%([\w.\-]+)", operands)[1]]
        else:
            continue
        if moved >= least_bytes:
            found.append(f"{name}: {op} of {moved / 2**20:.0f} MiB")
    return found


def test_delta_step_copies_nothing_it_keeps_at_published_widths(one_chip, no_compile_cache,
                                                                monkeypatch):
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
    layer, and the program fits the chip beside nothing else of its size."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from byteps_tpu.models import delta_moe as dm
    from byteps_tpu.models.transformer import build_train_step
    from byteps_tpu.ops import gated_delta_kernels as gk
    from byteps_tpu.parallel.mesh_utils import make_training_mesh

    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    cfg = dm.DeltaMoEConfig(vocab_size=18992, n_layers=4, experts_held=16,
                            compute_dtype=jnp.bfloat16)  # every width as published
    assert (cfg.n_periods, cfg.full_attention_interval, cfg.max_seq, cfg.remat) == (
        1, 4, 16384, True)
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
    assert _slices_moved(text, 64 * 10**6) == []
    kernels = [op_name for *_, kernel, op_name in _top_level(text) if kernel]
    assert tuple(sum(bool(re.search(rf"\b{name}\b", op_name)) for op_name in kernels)
                 for name in (gk.INVERSE_KERNEL, gk.FWD_KERNEL, gk.BWD_KERNEL)) == (3, 3, 3)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 10.2 * 2**30


def _bytes_of(shapes: str) -> int:
    return sum(math.prod(int(d) for d in dims.split(",") if d) * _ITEM[t]
               for t, dims in re.findall(_SHAPE, shapes))


def _top_level(text: str) -> list:
    """The ENTRY computation's instructions of an optimized module as (name,
    opcode, result shapes, operand names, is a Pallas kernel, ``op_name``)."""
    entry = text[text.index("\nENTRY "):]
    found = []
    for line in entry[:entry.index("\n}")].splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*)", line)
        if not m:
            continue
        body = m.group(2).split(", metadata=")[0]
        op = re.search(r" ([a-z\-]+)\(", body)
        args = body[op.end():].split(")", 1)[0]
        found.append((m.group(1), op.group(1), body[:op.start()],
                      [a.lstrip("%") for a in re.findall(r"%[\w.\-]+", args)],
                      "tpu_custom_call" in body,
                      (re.search(r'op_name="([^"]*)"', line) or ["", ""])[1]))
    return found


def _cuts_written_under(text: str, scope: str, least_bytes: int) -> list:
    """The top-level instructions under ``scope`` that WRITE columns cut out
    of an array, put back or rows shifted — a ``slice`` | ``pad`` |
    ``concatenate`` (dynamic ones too) of its own, or a fusion XLA named for
    one — in at least ``least_bytes``.  (A slice INSIDE a fusion is an index
    and writes nothing: the gated norm reads z's columns so.)"""
    return [f"{name}: {opcode} {result.strip()}" for name, opcode, result, _, kernel, path
            in _top_level(text)
            if scope in path and not kernel and _bytes_of(result) >= least_bytes
            and re.search(r"slice|pad|concatenate", name if opcode == "fusion" else opcode)]


def _kernel_operands(text: str, kernel: str) -> list:
    """The operands' types (``bf16[32,16384,128]``), in order, of the one
    Pallas call named ``kernel`` (a whole word of its ``op_name``)."""
    ops = _top_level(text)
    types = {name: re.sub(r"\{.*", "", result.strip().lstrip("(")) for name, _, result, *_ in ops}
    (call,) = [o for o in ops if o[4] and re.search(rf"\b{kernel}\b", o[5])]
    return [types[a] for a in call[3]]


def _named_bytes(text: str) -> int:
    """Bytes that the top-level operations OUTSIDE the Pallas kernels name:
    each ``fusion`` | ``copy`` | ``broadcast`` | ``reduce`` | ``convert``'s
    result and operands (a matrix product is a fusion here; the asynchronous
    copies that stage an operand for one are not counted twice)."""
    ops = _top_level(text)
    size = {name: _bytes_of(result) for name, _, result, *_ in ops}
    return sum(size[name] + sum(size.get(a, 0) for a in args)
               for name, opcode, _, args, kernel, _ in ops
               if not kernel and opcode in ("fusion", "copy", "broadcast", "reduce", "convert"))


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


def _sources(ops: list, name: str) -> list:
    """``name``'s operands, through ``get-tuple-element``s and ``bitcast``s."""
    by_name = {o[0]: o for o in ops}
    found, todo = [], [name]
    while todo:
        for arg in by_name[todo.pop()][3]:
            found.append(arg)
            if arg in by_name and by_name[arg][1] in ("get-tuple-element", "bitcast"):
                todo.append(arg)
    return found


def test_engine_split_compiles_for_the_largest_vgg16_leaf(one_chip, no_compile_cache):
    """VGG-16's first dense kernel, 25 088 × 4096 f32 flat, into the 101
    partitions ``engine.submit`` copies to the host: one program, 101
    outputs of a partition each, and no temporary beside them (the extra
    HBM of a step's prefetch is the gradient's own bytes, no more)."""
    from byteps_tpu.common.partition import partition_elements
    from byteps_tpu.core.engine import _split_program

    n = 25088 * 4096
    bounds = tuple((lo, lo + ln) for lo, ln in partition_elements(n, 4, 4_096_000))
    assert len(bounds) == 101
    flat = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = _split_program().trace(flat, bounds).lower(
        lowering_platforms=("tpu",)).compile()
    outs = compiled.out_info
    assert [o.shape for o in outs] == [(hi - lo,) for lo, hi in bounds]
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes == 0
    assert n * 4 <= memory.output_size_in_bytes < n * 4 + 101 * 4096


@pytest.mark.parametrize("window", [512, None], ids=["window_512", "full"])
def test_flash_kernels_compile_at_the_differential_attention_shape(one_chip, no_compile_cache,
                                                                   monkeypatch, window):
    """(1, 20 | 10, 16384, 64 | 128) — the cross-decoder family's mixers: one
    softmax of a differential pair is one call, queries and keys 64 wide, the
    value pair's one vector 128 wide, two query pairs a key/value pair; the
    banded pair at window 512 — the narrowest band the repo runs, 32 windows
    in a sequence — and the full causal pair of the full and cross layers."""
    monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
    q = jax.ShapeDtypeStruct((1, 20, 16384, 64), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 10, 16384, 64), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 10, 16384, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, scale=64 ** -0.5, window=window)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, v).as_text()
    wanted = (fa.FWD_WIN_KERNEL, fa.BWD_WIN_KERNEL) if window else (fa.FWD_KERNEL, fa.BWD_KERNEL)
    for kernel in wanted:
        assert kernel in text, f"{kernel} is not in the compiled program"
        assert tuple(_kernel_operands(text, kernel)[:3]) == (
            "bf16[20,16384,64]", "bf16[10,16384,64]", "bf16[10,16384,128]")
    assert (fa.FWD_WIN_KERNEL in text) == bool(window)


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
