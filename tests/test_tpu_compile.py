"""The flash kernels at the cells' real shapes, the held-expert layers and the
engine's split program, compiled for the chip without a chip
(tests/compiled_for_tpu.py says how, and why these cases are two files: the
mixers, the recurrences' layers and the whole steps are
tests/test_tpu_compile_layers.py).
"""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from byteps_tpu.ops import _dispatch

from compiled_for_tpu import (  # noqa: F401 (fixtures)
    _compile, _kernel_operands, no_compile_cache, one_chip)

fa = importlib.import_module("byteps_tpu.ops.flash_attention")


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
