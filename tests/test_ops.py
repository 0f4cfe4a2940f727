"""Pallas kernel tests (interpret mode on CPU; compiled on TPU).

Flash attention must match dense attention exactly; device onebit must be
bit-identical to the host/C++ codec's wire format.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.ops import _dispatch
from byteps_tpu.ops.flash_attention import _dense_reference, flash_attention
from byteps_tpu.ops.onebit_device import (
    onebit_compress_device,
    onebit_decompress_device,
    onebit_payload,
)

from family_cases import _kernel_names, _pallas_calls


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(2, 3, 256, 64)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(2, 3, 256, 64)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(2, 3, 256, 64)).astype(np.float32))
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                              interpret=True)
        ref = _dense_reference(q, k, v, causal, 64**-0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)

    def test_odd_shapes_fall_back(self):
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(1, 1, 100, 32)).astype(np.float32))
        out = flash_attention(q, q, q, causal=True)  # 100 % 128 != 0 → dense
        assert out.shape == q.shape
        assert np.all(np.isfinite(np.asarray(out)))

    def test_odd_shapes_raise_on_a_tpu(self, monkeypatch):
        """On a TPU the dense reference is never chosen quietly: a sequence
        the blocks do not divide is an error in both wrappers.  The platform
        is injected at the one probe (``_dispatch.platform``)."""
        import importlib

        fa = importlib.import_module("byteps_tpu.ops.flash_attention")
        monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
        q = jnp.zeros((1, 1, 200, 32), jnp.float32)  # 200 % 128 != 0
        with pytest.raises(ValueError, match="do not divide seq 200"):
            fa.flash_attention(q, q, q, causal=True)
        with pytest.raises(ValueError, match="do not divide seq 200"):
            fa.flash_attention_lse(q, q, q, interpret=True)
        assert fa._kernel_path(256, 128, 128, interpret=False) is True

    def test_grad_flows(self):
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.normal(size=(1, 2, 128, 32)).astype(np.float32))

        def loss(x):
            return jnp.sum(
                flash_attention(x, x, x, causal=True, block_q=64, block_k=64,
                                interpret=True) ** 2
            )

        g = jax.grad(loss)(q)
        assert np.all(np.isfinite(np.asarray(g)))

    # (causal, block_q, block_k, d_qk, d_v) at sequence 256: square blocks;
    # latent attention's 192 | 128 scaled down; block_q != block_k either way
    # round (8 key blocks of 32: dQ accumulates across the outer, sequential
    # axis; 2 query blocks a key block and 4 key blocks a query block on the
    # causal diagonal); one block a side
    @pytest.mark.parametrize("causal,bq,bk,d_qk,d_v", [
        (False, 64, 64, 32, 32), (True, 64, 64, 32, 32),
        (False, 64, 64, 48, 32), (True, 64, 64, 48, 32),
        (False, 128, 32, 48, 32), (True, 128, 32, 48, 32),
        (False, 32, 128, 48, 32), (True, 32, 128, 48, 32),
        (True, 256, 64, 32, 48), (True, 64, 256, 32, 48),
    ])
    def test_backward_kernel_matches_dense_grads(self, causal, bq, bk, d_qk, d_v):
        """The one backward kernel (scores, P, dP, dS once a block pair; dQ,
        dK and dV accumulated from them) must reproduce dense-attention
        gradients for independent q, k, v."""
        rng = np.random.default_rng(5)
        q, k, v, ct = (jnp.asarray(rng.normal(size=(2, 2, 256, d)).astype(np.float32))
                       for d in (d_qk, d_qk, d_v, d_v))

        def flash_loss(q, k, v):
            out = flash_attention(q, k, v, causal=causal, block_q=bq,
                                  block_k=bk, interpret=True)
            return jnp.sum(out * ct)

        def dense_loss(q, k, v):
            return jnp.sum(_dense_reference(q, k, v, causal, d_qk**-0.5) * ct)

        gq, gk, gv = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        rq, rk, rv = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(gq), np.asarray(rq), rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(rk), rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(rv), rtol=2e-3, atol=2e-4)

    # (group, causal, window, block_q, block_k, d_qk, d_v, lse output) at
    # sequence 256, two batches of 2 key/value heads: a key/value head found
    # by index map for its 2 | 4 | 7 query heads (7: SmallThinker's 28 | 4, a
    # group that is no power of two), full and banded, d_qk != d_v,
    # block_q != block_k both ways round, with and without the lse output
    @pytest.mark.parametrize("group,causal,window,bq,bk,d_qk,d_v,lse_out", [
        (2, True, None, 64, 64, 32, 32, False), (4, True, None, 64, 64, 48, 32, False),
        (2, False, None, 128, 32, 48, 32, False), (4, False, None, 32, 128, 32, 48, True),
        (2, True, None, 64, 32, 48, 32, True), (4, True, None, 256, 64, 32, 48, True),
        (2, True, 64, 64, 64, 32, 32, False), (4, True, 100, 64, 64, 48, 32, False),
        (2, True, 37, 32, 32, 32, 48, True), (4, True, 64, 128, 32, 48, 32, True),
        (4, True, 96, 32, 128, 32, 48, False), (2, True, 1, 64, 64, 32, 32, False),
        (7, True, None, 64, 64, 32, 32, False), (7, True, None, 128, 32, 48, 32, True),
        (7, True, 64, 64, 64, 32, 32, False), (7, True, 100, 32, 128, 32, 48, True),
    ])
    def test_grouped_heads_by_index_map_match_repeated_heads(self, group, causal, window, bq, bk,
                                                              d_qk, d_v, lse_out):
        """K and V with fewer heads than Q: the kernels (interpret mode)
        against the dense reference on heads repeated BY THE CALLER — output,
        lse where asked, and dQ, dK, dV (a key/value head's gradient is the
        sum over its group: the repeat's transpose)."""
        import importlib

        fa = importlib.import_module("byteps_tpu.ops.flash_attention")
        rng = np.random.default_rng(11)
        b, h_kv = 2, 2
        h = h_kv * group
        q, k, v, ct = (jnp.asarray(rng.normal(size=(b, heads, 256, d)).astype(np.float32))
                       for heads, d in ((h, d_qk), (h_kv, d_qk), (h_kv, d_v), (h, d_v)))

        def loss(attend):
            def f(q, k, v):
                o, lse = attend(q, k, v)
                return jnp.sum(o * ct) + (jnp.sum(jnp.sin(lse)) if lse_out else 0.0), (o, lse)
            (_, outs), grads = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return outs + grads

        kw = dict(causal=causal, block_q=bq, block_k=bk, interpret=True, window=window)
        if lse_out:
            kernels = lambda q, k, v: fa.flash_attention_lse(q, k, v, **kw)  # noqa: E731
        else:
            kernels = lambda q, k, v: (fa.flash_attention(q, k, v, **kw), jnp.zeros(()))  # noqa: E731
        repeated = lambda q, k, v: fa._dense_reference_lse(  # noqa: E731
            q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1), causal, d_qk ** -0.5,
            window)
        got, want = loss(kernels), loss(repeated)
        assert got[3].shape == k.shape and got[4].shape == v.shape
        for name, g, w in zip(("out", "lse", "dQ", "dK", "dV"), got, want):
            if name == "lse" and not lse_out:
                continue
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-4,
                                       err_msg=name)

    @pytest.mark.parametrize("window", [None, 40])
    def test_grouped_heads_off_the_kernels_take_the_dense_reference(self, window):
        """Off a TPU and without the interpreter the dense reference stands
        in and repeats inside: the same numbers as repeated operands."""
        rng = np.random.default_rng(12)
        q, k, v = (jnp.asarray(rng.normal(size=(1, heads, 96, 16)).astype(np.float32))
                   for heads in (6, 2, 2))
        got = flash_attention(q, k, v, causal=True, window=window)
        want = flash_attention(q, jnp.repeat(k, 3, axis=1), jnp.repeat(v, 3, axis=1), causal=True,
                               window=window)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("heads", [(4, 3, 3), (4, 2, 4), (4, 8, 8)])
    def test_head_counts_that_form_no_groups_raise(self, heads):
        import importlib

        fa = importlib.import_module("byteps_tpu.ops.flash_attention")
        q, k, v = (jnp.zeros((1, n, 64, 16)) for n in heads)
        for attend in (fa.flash_attention, fa.flash_attention_lse):
            with pytest.raises(ValueError, match="divides the 4 query heads"):
                attend(q, k, v, causal=True)

    def test_grouped_kernels_take_k_and_v_at_their_own_head_count(self):
        """The engaged mechanism, read from the traced program: both kernel
        calls take K and V as (batch · h_kv, s, d) — no repeated copy is an
        operand —, Q first and V third, and the K/V index maps divide the
        grid's row by the group."""
        import importlib

        fa = importlib.import_module("byteps_tpu.ops.flash_attention")
        q, k = jnp.ones((2, 8, 128, 32), jnp.float32), jnp.ones((2, 2, 128, 32), jnp.float32)
        v = jnp.ones((2, 2, 128, 48), jnp.float32)

        grad = jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, causal=True, block_q=64, block_k=32, interpret=True)), argnums=(0, 1, 2))
        found = dict(_pallas_calls(jax.make_jaxpr(grad)(q, k, v).jaxpr))
        assert found[fa.FWD_KERNEL] == [(16, 128, 32), (4, 128, 32), (4, 128, 48)]
        assert found[fa.BWD_KERNEL][:4] == [(16, 128, 32), (4, 128, 32), (4, 128, 48), (16, 128, 48)]
        index = fa._kv_index(True, 64, 32, None, 4)
        assert [index(i, 1, 0)[0] for i in (0, 3, 4, 15)] == [0, 0, 1, 3]
        same = fa._kv_index(True, 64, 32)
        assert fa._kv_row(same, 1) is same  # equal head counts: the map as it was

    @pytest.mark.parametrize("lse_out", [False, True])
    def test_backward_is_one_kernel(self, lse_out):
        """Every backward flash call is the one kernel: a gradient's program
        holds one ``flash_fwd`` and one ``flash_bwd`` and no third."""
        import importlib

        fa = importlib.import_module("byteps_tpu.ops.flash_attention")
        q = jnp.ones((1, 2, 128, 32), jnp.float32)

        def loss(q, k, v):
            if lse_out:
                out, lse = fa.flash_attention_lse(q, k, v, causal=True, block_q=64,
                                                  block_k=32, interpret=True)
                return jnp.sum(out) + jnp.sum(lse)
            return jnp.sum(fa.flash_attention(q, k, v, causal=True, block_q=64,
                                              block_k=32, interpret=True))

        names = _kernel_names(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
        assert names == [fa.BWD_KERNEL, fa.FWD_KERNEL]
        assert not fa.BWD_KERNEL.startswith(("flash_bwd_dq", "flash_bwd_dkv"))

    def test_backward_states_its_vmem(self):
        """The kernel's VMEM limit comes from its shapes: dQ's f32
        accumulator over the whole sequence of one (batch·head) is inside
        it, and the three cells' shapes stay under a v5e's 128 MiB."""
        import importlib

        fa = importlib.import_module("byteps_tpu.ops.flash_attention")
        for s, (d_qk, d_v) in ((8192, (192, 128)), (16384, (256, 256)), (8192, (64, 64))):
            bq, bk = fa.tuned_blocks(s)
            asked = fa._bwd_vmem_bytes(s, bq, bk, d_qk, d_v, 2)
            lanes = -(-d_qk // fa.LANES) * fa.LANES
            assert s * lanes * 4 < asked < 100 * 2**20, (s, d_qk, asked)


class TestFlashBand:
    """``window=W``: query i sees key j iff 0 <= i - j < W.  The banded
    kernels (interpret mode) against the dense mask, forward and dQ, dK, dV."""

    @staticmethod
    def _fa():
        import importlib

        return importlib.import_module("byteps_tpu.ops.flash_attention")

    @staticmethod
    def _qkv(d_qk=32, d_v=32, s=256, seed=7):
        rng = np.random.default_rng(seed)
        return tuple(jnp.asarray(rng.normal(size=(1, 2, s, d)).astype(np.float32))
                     for d in (d_qk, d_qk, d_v, d_v))

    def _both(self, window, bq, bk, d_qk=32, d_v=32, s=256):
        """((out, dQ, dK, dV) of the kernels, the same of the dense mask)."""
        fa = self._fa()
        q, k, v, ct = self._qkv(d_qk, d_v, s)

        def run(attend):
            out, grads = jax.value_and_grad(
                lambda q, k, v: (lambda o: (jnp.sum(o * ct), o))(attend(q, k, v)),
                argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return (out[1],) + grads

        return (run(lambda q, k, v: fa.flash_attention(
                    q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True, window=window)),
                run(lambda q, k, v: fa._dense_reference(q, k, v, True, d_qk ** -0.5, window)))

    def test_the_dense_mask_is_the_published_one(self):
        """kv > q - sliding_window, causal: at window 2 a query sees itself
        and the key before it."""
        fa = self._fa()
        q = jnp.zeros((1, 1, 4, 8))
        v = jnp.eye(4)[None, None]
        got = fa._dense_reference(q, q, v, True, 1.0, 2)[0, 0]  # uniform over what is seen
        np.testing.assert_allclose(got, [[1, 0, 0, 0], [.5, .5, 0, 0], [0, .5, .5, 0],
                                         [0, 0, .5, .5]], atol=1e-6)

    # (window, block_q, block_k, d_qk, d_v) at sequence 256: W under, at and
    # over a block; W no multiple of the block; W = 1 (the diagonal alone);
    # block_q != block_k both ways round; d_qk != d_v; one block a side
    @pytest.mark.parametrize("window,bq,bk,d_qk,d_v", [
        (32, 64, 64, 32, 32), (64, 64, 64, 32, 32), (128, 64, 64, 32, 32),
        (100, 64, 64, 48, 32), (1, 64, 64, 32, 32), (37, 32, 32, 32, 48),
        (64, 128, 32, 48, 32), (64, 32, 128, 48, 32), (96, 256, 64, 32, 32),
        (70, 64, 256, 32, 48), (200, 256, 256, 32, 32),
    ])
    def test_band_matches_the_dense_mask_forward_and_backward(self, window, bq, bk, d_qk, d_v):
        got, want = self._both(window, bq, bk, d_qk, d_v)
        for name, g, w in zip(("out", "dQ", "dK", "dV"), got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-4,
                                       err_msg=name)

    @pytest.mark.parametrize("window,bq,bk", [(256, 64, 64), (1000, 64, 32), (256, 128, 256)])
    def test_a_window_over_the_sequence_is_causal_bit_for_bit(self, window, bq, bk):
        fa = self._fa()
        q, k, v, ct = self._qkv()

        def run(**kw):
            return jax.value_and_grad(
                lambda q, k, v: (lambda o: (jnp.sum(o * ct), o))(fa.flash_attention(
                    q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True, **kw)),
                argnums=(0, 1, 2), has_aux=True)(q, k, v)

        (_, out_w), grads_w = run(window=window)
        (_, out_c), grads_c = run()
        for a, b in zip((out_w,) + grads_w, (out_c,) + grads_c):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_every_query_block_of_dq_is_written(self):
        """At a window the LAST key block meets only the last query blocks:
        dQ's blocks are written as their own pairs pass, not while the last
        key block's steps do.  Sixteen query blocks, a band at most three
        blocks wide: every block of dQ holds its gradient."""
        fa = self._fa()
        got, want = self._both(20, 16, 16)
        assert fa._band_steps(256, 16, 16, 20) == (3, 3)
        dq = np.asarray(got[1])
        assert np.all(np.isfinite(dq))
        for block in range(16):
            rows = slice(16 * block, 16 * (block + 1))
            assert np.any(dq[:, :, rows] != 0), block
            np.testing.assert_allclose(dq[:, :, rows], np.asarray(want[1])[:, :, rows],
                                       rtol=2e-3, atol=2e-4)

    def test_the_band_is_as_wide_as_its_blocks_not_as_the_sequence(self):
        """The banded kernels' innermost grid axis: 3 blocks at the cell's
        1024 x 1024 over 16 384 tokens (16 without the band), 5 at 512."""
        fa = self._fa()
        assert fa._band_steps(16384, 1024, 1024, 2048) == (3, 3)
        assert fa._band_steps(16384, 512, 512, 2048) == (5, 5)
        assert fa._band_steps(16384, 1024, 512, 2048) == (6, 3)
        assert fa._band_steps(256, 64, 64, 256) == (4, 4)  # the whole causal triangle

    def test_lse_output_and_its_cotangent_under_a_window(self):
        fa = self._fa()
        q, k, v, ct = self._qkv()

        def loss(attend):
            def f(q, k, v):
                o, lse = attend(q, k, v)
                return jnp.sum(o * ct) + jnp.sum(jnp.sin(lse))
            return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

        got = loss(lambda q, k, v: fa.flash_attention_lse(
            q, k, v, causal=True, block_q=64, block_k=32, interpret=True, window=48))
        want = loss(lambda q, k, v: fa._dense_reference_lse(q, k, v, True, 32 ** -0.5, 48))
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for g, w in zip(got[1], want[1]):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-4)

    @pytest.mark.parametrize("kw", [dict(causal=False, window=8), dict(causal=True, window=0)])
    def test_a_window_that_is_no_causal_band_raises(self, kw):
        fa = self._fa()
        q = jnp.zeros((1, 1, 64, 16))
        for attend in (fa.flash_attention, fa.flash_attention_lse):
            with pytest.raises(ValueError, match="causal band"):
                attend(q, q, q, **kw)

    def test_banded_calls_carry_their_own_kernel_names(self):
        fa = self._fa()
        q = jnp.ones((1, 2, 128, 32), jnp.float32)

        def loss(q, k, v):
            return jnp.sum(fa.flash_attention(q, k, v, causal=True, block_q=64, block_k=32,
                                              interpret=True, window=40))

        names = _kernel_names(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
        assert names == ["flash_bwd_win", "flash_fwd_win"]
        assert (fa.FWD_WIN_KERNEL, fa.BWD_WIN_KERNEL) == ("flash_fwd_win", "flash_bwd_win")

    #: sha256 of what ``window=None`` lowers to, frozen at the parent of the PR
    #: that brought the band: the StableHLO text of a gradient's program in
    #: interpret mode ((causal, lse output) → digest), and the jaxpr of the
    #: TPU path at qwen3-next's shape, kernels' bodies included (a Mosaic
    #: module's own text holds the file's line numbers and cannot be frozen)
    FROZEN_WITHOUT_A_WINDOW = {
        (True, False): "6d387562cec12f1e", (True, True): "15062387814322b4",
        (False, False): "86d5adc5ec66e202", (False, True): "d2905411e9475f56",
        "tpu_jaxpr": "410cbf3fa02cea60",
        # re-taken on purpose at PR 65: at 1024 x 1024 the diagonal tiles are
        # walked by sub-blocks; with no tile lined up the text above stands
        "tpu_jaxpr_sub_blocks": "8a7ea95ee63ce71c",
    }

    @pytest.mark.parametrize("causal,lse_out", [(True, False), (True, True), (False, False),
                                                (False, True)])
    def test_without_a_window_the_kernels_lower_as_before(self, causal, lse_out):
        import hashlib

        fa = self._fa()

        def loss(q, k, v):
            if lse_out:
                o, lse = fa.flash_attention_lse(q, k, v, causal=causal, block_q=64, block_k=32,
                                                interpret=True)
                return jnp.sum(o) + jnp.sum(lse)
            return jnp.sum(fa.flash_attention(q, k, v, causal=causal, block_q=64, block_k=32,
                                              interpret=True))

        q, v = jnp.ones((1, 2, 128, 32), jnp.float32), jnp.ones((1, 2, 128, 48), jnp.float32)
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, v).as_text()
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
            self.FROZEN_WITHOUT_A_WINDOW[causal, lse_out]

    @pytest.mark.parametrize("held,side", [("tpu_jaxpr", 1 << 30), ("tpu_jaxpr_sub_blocks", 256)])
    def test_without_a_window_the_tpu_path_traces_as_before(self, monkeypatch, held, side):
        import hashlib

        fa = self._fa()
        monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
        monkeypatch.setattr(fa, "SUB_BLOCK", side)
        q = jax.ShapeDtypeStruct((1, 16, 16384, 256), jnp.bfloat16)
        grad = jax.grad(lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, causal=True).astype(jnp.float32)), argnums=(0, 1, 2))
        text = str(jax.make_jaxpr(grad)(q, q, q))
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
            self.FROZEN_WITHOUT_A_WINDOW[held]

    def test_banded_blocks_have_a_table_of_their_own(self, monkeypatch, tmp_path):
        import json

        fa = self._fa()
        path = tmp_path / "flash_blocks.json"
        path.write_text(json.dumps({"blocks": {"512": [256, 256]},
                                    "banded": {"512,128": [64, 128], "512,96": [100, 100]}}))
        monkeypatch.setattr(fa, "_TUNED_PATH", str(path))
        assert fa.tuned_blocks(512) == (256, 256)  # the plain entry, untouched
        assert fa.tuned_blocks(512, 128) == (64, 128)
        assert fa.tuned_blocks(512, 64) == (256, 256)  # no banded entry: the sequence's
        assert fa.tuned_blocks(512, 96) == (256, 256)  # one that does not divide is not used

    def test_the_committed_tables(self, monkeypatch):
        """The existing entries stand as they were; the cell's banded entry is there."""
        fa = self._fa()
        assert [fa.tuned_blocks(s) for s in (512, 1024, 2048, 8192, 16384)] == [
            (512, 512), (512, 512), (512, 512), (1024, 1024), (1024, 1024)]
        bq, bk = fa.tuned_blocks(16384, 2048)
        assert 16384 % bq == 0 and 16384 % bk == 0 and (16384, 2048) in fa._tuned_table()["banded"]


class TestFlashSubBlocks:
    """A partial tile is walked by sub-blocks: the lists against the masks'
    predicates entry by entry, the kernels on that path (interpret mode)
    against the dense masks and against the whole-tile path, and the count of
    what a call computes."""

    _fa = staticmethod(TestFlashBand._fa)

    @staticmethod
    def _constants(monkeypatch, side, least=0.2):
        fa = TestFlashBand._fa()
        monkeypatch.setattr(fa, "SUB_BLOCK", side)
        monkeypatch.setattr(fa, "MIN_SPARED", least)
        return fa

    @classmethod
    def _kinds(cls, fa, family, tile, side):
        """[(tile's first row, first column, its sub-block map)] of a family's
        kinds at (tile, tile) tiles: ("band", window) over any sequence,
        ("bd", block length, queries, key rows)."""
        if family[0] == "band":
            maps = fa._band_kinds(tile, tile, family[1], side, 0.01)
            return [(o * tile, 0, m) for o, m in maps.items()]
        _, block, sq, sk = family
        kind, maps, _ = fa._bd_kinds(sq, sk // 2, block, tile, tile, side, 0.01)
        firsts = [np.argwhere(kind == i + 1)[0] for i in range(len(maps))]
        return [(qi * tile, j * tile, m) for (qi, j), m in zip(firsts, maps)]

    @staticmethod
    def _visible(fa, family, rows, cols):
        """The mask by the dense references' own rule, not the lists'."""
        if family[0] == "band":
            seen = rows >= cols
            return seen if family[1] is None else seen & (cols > rows - family[1])
        return np.asarray(fa.block_diffusion_visible(rows, cols, family[3] // 2, family[1]))

    # (family, tile, sub-block side, kinds expected, listed sub-blocks of each)
    @pytest.mark.parametrize("family,tile,side,listed", [
        (("band", None), 1024, 256, [10]), (("band", 4096), 1024, 256, [10, 10]),
        (("band", 2048), 1024, 256, [10, 10]), (("band", 512), 1024, 256, [9, 3]),
        (("band", None), 1024, 128, [36]), (("band", None), 1024, 512, [3]),
        (("bd", 4, 16384, 16384), 1024, 256, [4, 10]), (("bd", 4, 8192, 16384), 1024, 256, [4, 10]),
        (("bd", 32, 16384, 16384), 1024, 256, [4, 10]),
        (("band", None), 64, 16, [10]), (("band", 64), 64, 16, [10, 10]),
        (("band", 100), 64, 16, [10, 6]), (("band", 37), 64, 16, [10, 6]),
        (("band", 200), 64, 16, [10, 13, 1]), (("bd", 4, 256, 256), 64, 16, [4, 10]),
        (("bd", 4, 128, 256), 64, 16, [4, 10]), (("bd", 32, 256, 256), 64, 16, [8, 4, 12]),
    ], ids=str)
    @pytest.mark.parametrize("by", ["q", "k"])
    def test_the_listed_rectangles_cover_what_the_mask_keeps(self, family, tile, side, listed, by):
        fa = self._fa()
        kinds = self._kinds(fa, family, tile, side)
        assert [int(m.sum()) for _, _, m in kinds] == listed
        for row0, col0, sub_map in kinds:
            rows, cols = np.ogrid[row0:row0 + tile, col0:col0 + tile]
            seen = self._visible(fa, family, rows, cols)
            covered = np.zeros((tile, tile), int)
            for r0, r1, c0, c1 in fa._rectangles(sub_map, side, by):
                assert r0 % side == r1 % side == c0 % side == c1 % side == 0
                covered[r0:r1, c0:c1] += 1
            assert covered.max() == 1, "two rectangles overlap"
            assert not np.any(seen & (covered == 0)), "a visible entry is in no rectangle"
            per_block = (seen & (covered == 1)).reshape(
                tile // side, side, tile // side, side).any(axis=(1, 3))
            listed_blocks = covered.reshape(tile // side, side, tile // side, side).all(axis=(1, 3))
            np.testing.assert_array_equal(per_block, listed_blocks,
                                          err_msg="a listed sub-block is wholly hidden")
            np.testing.assert_array_equal(listed_blocks, sub_map)

    def test_two_quadrants_that_list_the_same_sub_blocks_are_one_kind(self):
        """SDAR's call: the noisy x noisy diagonal is one kind (4 of 16), the
        noisy x clean and clean x clean diagonals share the other (10 of 16),
        and no partial tile is left on the whole-tile path."""
        fa = self._fa()
        kind, maps, rest = fa._bd_kinds(16384, 8192, 4, 1024, 1024, 256, 0.3)
        assert [int(m.sum()) for m in maps] == [4, 10] and not rest
        assert np.bincount(kind.ravel()).tolist() == [256 - 24, 8, 16]
        assert all(kind[i, i] == 1 and kind[i, 8 + i] == 2 and kind[8 + i, 8 + i] == 2
                   for i in range(8))

    def test_too_many_kinds_or_too_little_spared_take_the_whole_tile(self):
        fa = self._fa()
        assert fa._band_kinds(1024, 1024, None, 512, 0.3) == {}  # 3 of 4 spares a quarter
        assert sorted(fa._band_kinds(1024, 1024, None, 512, 0.25)) == [0]
        assert fa._band_kinds(1024, 512, 2048, 256, 0.1) == {}  # bq != bk
        assert fa._band_kinds(256, 256, None, 256, 0.1) == {}  # one sub-block a side
        kind, maps, rest = fa._bd_kinds(256, 128, 32, 64, 64, 16, 0.2)
        assert len(maps) == 3 and fa.MAX_KINDS >= 3

    @staticmethod
    def _run(attend, q, k, v, ct, cl):
        def loss(q, k, v):
            out, lse = attend(q, k, v)
            return jnp.sum(out * ct) + jnp.sum(jnp.sin(lse) * cl), (out, lse)
        (_, aux), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (*aux, *grads)

    # grouped heads (4 | 2) and d_qk != d_v (32 | 48) in every case; tiles of 64,
    # sub-blocks of 16: causal; a window that is a multiple of the tile, one that
    # is not, one under the tile; block diffusion at 4 and 32, both copies'
    # queries and the noised copy's alone
    @pytest.mark.parametrize("mask", [
        ("band", None), ("band", 64), ("band", 100), ("band", 37),
        ("bd", 4, 256, 256), ("bd", 32, 256, 256), ("bd", 4, 128, 256), ("bd", 32, 128, 256),
    ], ids=str)
    def test_the_sub_block_path_matches_the_dense_mask_and_the_whole_tile_path(
            self, monkeypatch, mask):
        fa = self._constants(monkeypatch, 16)
        rng = np.random.default_rng(65)
        sq, sk = (256, 256) if mask[0] == "band" else mask[2:]
        q, k, v, ct, cl = (jnp.asarray(rng.normal(size=shape).astype(np.float32)) for shape in (
            (1, 4, sq, 32), (1, 2, sk, 32), (1, 2, sk, 48), (1, 4, sq, 48), (1, 4, sq)))
        if mask[0] == "band":
            kernels = lambda q, k, v: fa.flash_attention_lse(  # noqa: E731
                q, k, v, causal=True, block_q=64, block_k=64, interpret=True, window=mask[1])
            dense = lambda q, k, v: fa._dense_reference_lse(  # noqa: E731
                q, k, v, True, 32 ** -0.5, mask[1])
            counts = dict(window=mask[1])
        else:
            kernels = lambda q, k, v: fa.block_diffusion_attention_lse(  # noqa: E731
                q, k, v, mask[1], block_q=64, block_k=64, interpret=True)
            dense = lambda q, k, v: fa._dense_block_diffusion_lse(  # noqa: E731
                q, k, v, mask[1], 32 ** -0.5)
            counts = dict(block_length=mask[1])
            assert not fa._bd_kinds(sq, sk // 2, mask[1], 64, 64, 16, 0.2)[2]
        # every partial tile takes the path: nothing computed but listed sub-blocks
        computed, kept = fa.computed_entries(sq, sk, 64, 64, **counts)
        whole, _ = fa.computed_entries(sq, sk, 64, 64, whole_tiles=True, **counts)
        assert kept <= computed < whole
        got = self._run(kernels, q, k, v, ct, cl)
        want = self._run(dense, q, k, v, ct, cl)
        monkeypatch.setattr(fa, "SUB_BLOCK", 1 << 30)
        assert fa.computed_entries(sq, sk, 64, 64, **counts)[0] == whole
        whole_tiles = self._run(kernels, q, k, v, ct, cl)
        for name, g, w, t in zip(("out", "lse", "dQ", "dK", "dV"), got, want, whole_tiles):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-4,
                                       err_msg=f"{name} against the dense mask")
            np.testing.assert_allclose(np.asarray(g), np.asarray(t), rtol=1e-5, atol=1e-5,
                                       err_msg=f"{name} against the whole-tile path")

    @pytest.mark.parametrize("strips", ["qq", "kk", "kq"])
    def test_either_cut_in_either_direction_is_the_same_attention(self, monkeypatch, strips):
        fa = self._constants(monkeypatch, 16)
        monkeypatch.setattr(fa, "STRIPS", {"fwd": strips[0], "bwd": strips[1]})
        got, want = TestFlashBand()._both(100, 64, 64, 32, 48)
        for name, g, w in zip(("out", "dQ", "dK", "dV"), got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-4,
                                       err_msg=name)

    # a geometry that does not line up: bq != bk; a tile of one sub-block a
    # side; a tile that is no whole sub-blocks — under sub-blocks of 16 | 64 | 48 |
    # 12 the text is the one a side no tile can hold gives
    @pytest.mark.parametrize("mask,bq,bk,side", [
        (("band", None), 64, 32, 16), (("band", 40), 64, 32, 16), (("band", 40), 64, 64, 64),
        (("band", None), 64, 64, 48), (("bd", 4), 16, 16, 16), (("bd", 4), 32, 16, 12),
    ], ids=str)
    def test_a_geometry_that_does_not_line_up_lowers_as_before(self, monkeypatch, mask, bq, bk,
                                                                side):
        fa = self._fa()
        q, k = jnp.ones((1, 2, 128, 32), jnp.float32), jnp.ones((1, 1, 128, 32), jnp.float32)

        def loss(q, k, v):
            if mask[0] == "bd":
                return jnp.sum(fa.block_diffusion_attention(
                    q, k, v, mask[1], block_q=bq, block_k=bk, interpret=True))
            return jnp.sum(fa.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                                              interpret=True, window=mask[1]))

        def text(sub):
            monkeypatch.setattr(fa, "SUB_BLOCK", sub)
            monkeypatch.setattr(fa, "MIN_SPARED", 0.01)
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, k).as_text()

        assert text(side) == text(1 << 30)

    # the issue's table: tile-equivalents a head computes, of those whole tiles
    # would, at 1024 x 1024 tiles, sub-blocks of 256 and every 10-of-16 kind in
    @pytest.mark.parametrize("sq,sk,mask,computed,whole", [
        (16384, 16384, dict(block_length=4), 68, 80), (8192, 16384, dict(block_length=4), 35, 44),
        (16384, 16384, dict(window=4096), 59.5, 70), (16384, 16384, dict(window=2048), 33.75, 45),
        (16384, 16384, dict(window=512), 11.8125, 31), (8192, 8192, {}, 33, 36),
        (16384, 16384, {}, 130, 136),
    ], ids=str)
    def test_computed_entries_counts_the_listed_sub_blocks(self, monkeypatch, sq, sk, mask,
                                                           computed, whole):
        fa = self._constants(monkeypatch, 256, 0.3)
        got, kept = fa.computed_entries(sq, sk, 1024, 1024, **mask)
        assert got == computed * 1024 ** 2
        assert fa.computed_entries(sq, sk, 1024, 1024, whole_tiles=True, **mask) == (
            whole * 1024 ** 2, kept)
        rows, cols = np.ogrid[:sq // 16, :sk // 16]  # the same mask at a sixteenth the rows
        if "block_length" in mask:
            small = fa.computed_entries(sq // 16, sk // 16, 64, 64, block_length=4)[1]
            assert small == int(fa.block_diffusion_visible(rows, cols, sk // 32, 4).sum())
            half = sk // 2
            assert kept == half * 4 + 16 * (half // 4) * (half // 4 - 1) // 2 + (
                16 * (half // 4) * (half // 4 + 1) // 2 if sq == sk else 0)
        else:
            window = mask.get("window")
            small = fa.computed_entries(sq // 16, sk // 16, 64, 64,
                                        window=window and window // 16)[1]
            assert small == int(fa._band_visible(rows, cols, window and window // 16).sum())

    def test_the_committed_constants_spare_sdar_what_the_sweep_found(self):
        """At the constants the module commits (one on-chip sweep, recorded in
        flash_blocks.json's source texts) SDAR's call computes 68 of the 80
        tiles' entries it computed whole."""
        fa = self._fa()
        assert (fa.SUB_BLOCK, fa.STRIPS) == (256, {"fwd": "q", "bwd": "k"})
        computed, kept = fa.computed_entries(16384, 16384, 1024, 1024, block_length=4)
        assert computed == 68 * 1024 ** 2 and kept / computed > 0.94


class TestHeadNormRope:
    """``ops/head_norm.head_norm_rope``: a head's RMSNorm and its rotation in
    one pass each way, held to ``rms`` then ``rope_partial`` (the two passes
    the sliding-window family's mixers made), XLA's form and the kernels
    (interpret mode) alike."""

    @staticmethod
    def _old(x, w, eps, theta):
        from byteps_tpu.models.moe_family import rms, rope_partial

        y = rms(x, w, eps).astype(x.dtype)
        return y if theta is None else rope_partial(y, x.shape[-1], theta)

    @staticmethod
    def _inputs(shape, dtype=jnp.float32, seed=21):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=shape).astype(np.float32) * 1.7, dtype)
        w = jnp.asarray(1 + 0.2 * rng.normal(size=shape[-1:]).astype(np.float32))
        ct = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        return x, w, ct

    # (shape, theta, interpret): heads of a lane tile take the kernels under
    # the interpreter (one block and several a sequence), anything else XLA's form
    @pytest.mark.parametrize("shape,theta,interpret", [
        ((2, 3, 256, 128), 10000.0, True), ((2, 3, 256, 128), None, True),
        ((1, 2, 2048, 128), 10000.0, True), ((1, 2, 2048, 256), 500000.0, True),
        ((2, 3, 256, 128), 10000.0, False), ((2, 4, 16, 8), 10000.0, False),
        ((2, 4, 16, 8), None, False), ((1, 2, 24, 6), 10000.0, True),
    ])
    def test_matches_norm_then_rope_and_its_gradient(self, shape, theta, interpret):
        """f32 in: the output, dx and the scale's gradient are ``rms`` then
        ``rope_partial``'s to 1e-6."""
        from byteps_tpu.ops import head_norm as hn

        assert hn._kernel_path(shape[-2], shape[-1], interpret) == \
            (interpret and shape[-1] % 128 == 0)
        x, w, ct = self._inputs(shape)

        def run(f):
            return (f(x, w),) + jax.grad(lambda x, w: jnp.sum(f(x, w) * ct), argnums=(0, 1))(x, w)

        got = run(lambda x, w: hn.head_norm_rope(x, w, 1e-5, theta, interpret=interpret))
        want = run(lambda x, w: self._old(x, w, 1e-5, theta))
        for name, g, r in zip(("out", "dx", "dw"), got, want):
            assert g.shape == r.shape and g.dtype == r.dtype
            scale = float(jnp.max(jnp.abs(r)))
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=0, atol=1e-6 * scale,
                                       err_msg=name)

    @pytest.mark.parametrize("theta", [10000.0, None])
    def test_bf16_in_is_f32_arithmetic_rounded_once(self, theta):
        """bf16 in and out, nothing between them rounded: the result is the
        f32 result of the same bf16 values, rounded — by the kernels and by
        XLA's form, to the bit —, where norm then rope rounded twice."""
        from byteps_tpu.ops import head_norm as hn

        x, w, ct = self._inputs((1, 2, 256, 128), jnp.bfloat16)
        exact = self._old(x.astype(jnp.float32), w, 1e-5, theta)
        for interpret in (True, False):
            y = hn.head_norm_rope(x, w, 1e-5, theta, interpret=interpret)
            assert y.dtype == jnp.bfloat16
            off = np.abs(np.asarray(y, np.float32) - np.asarray(exact))
            # one rounding to bf16's 8 bits: half a unit in the last place
            assert np.all(off <= np.abs(np.asarray(exact)) * 2.0 ** -8 + 1e-30)
            dx, dw = jax.grad(lambda x, w: jnp.sum(hn.head_norm_rope(
                x, w, 1e-5, theta, interpret=interpret).astype(jnp.float32) * ct),
                argnums=(0, 1))(x, w)
            assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.float32

    def test_statistics_rotation_and_scale_are_f32(self):
        """The stated precision, read from the traced program of a bf16 call:
        the square, its mean, the rsqrt, the tables and every product are f32;
        bf16 appears only as the input and the one final rounding."""
        from byteps_tpu.ops import head_norm as hn

        x, w, _ = self._inputs((1, 2, 16, 8), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(lambda x, w: hn._fwd(x, w, 1e-5, 10000.0, False)[0])(x, w)
        arithmetic = [e for e in jaxpr.eqns if e.primitive.name in
                      ("mul", "add", "sub", "rsqrt", "reduce_sum", "div", "cos", "sin", "integer_pow")]
        assert arithmetic and all(v.aval.dtype == jnp.float32
                                  for e in arithmetic for v in e.outvars)
        casts = [e for e in jaxpr.eqns if e.primitive.name == "convert_element_type"
                 and e.outvars[0].aval.dtype == jnp.bfloat16]
        assert len(casts) == 1

    def test_an_odd_head_has_no_halves(self):
        from byteps_tpu.ops import head_norm as hn

        with pytest.raises(ValueError, match="no halves"):
            hn.head_norm_rope(jnp.ones((1, 1, 4, 7)), jnp.ones((7,)), 1e-5, 10000.0)

    def test_the_kernels_are_the_program_where_heads_tile(self, monkeypatch):
        """On a TPU at heads of a lane tile both passes are one Pallas call
        each — ``head_norm_fwd``, ``head_norm_bwd`` —, read from the traced
        gradient; at a head that does not tile, none."""
        from byteps_tpu.ops import head_norm as hn

        monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")

        grad = jax.grad(lambda x, w: jnp.sum(hn.head_norm_rope(x, w, 1e-5, 10000.0)
                                             .astype(jnp.float32)), argnums=(0, 1))
        x = jax.ShapeDtypeStruct((1, 4, 2048, 128), jnp.bfloat16)
        w = jax.ShapeDtypeStruct((128,), jnp.float32)
        assert _kernel_names(grad, x, w) == [hn.BWD_KERNEL, hn.FWD_KERNEL]
        small = jax.ShapeDtypeStruct((1, 4, 2048, 64), jnp.bfloat16)
        assert _kernel_names(grad, small, jax.ShapeDtypeStruct((64,), jnp.float32)) == []


class TestHeadRope:
    """``ops/head_norm.head_rope``: the rotation alone, one pass each way —
    a token-major product's heads turned over the whole head and written
    head-major — held to ``moe_family.rope_partial`` at ``rotary_dim`` = the
    head (the pass the early-routed family's sliding mixers made), XLA's form
    and the kernels (interpret mode) alike."""

    @staticmethod
    def _old(x, d, theta):
        from byteps_tpu.models.moe_family import rope_partial

        b, s, f = x.shape
        return rope_partial(jnp.swapaxes(x.reshape(b, s, f // d, d), 1, 2), d, theta)

    @staticmethod
    def _inputs(b, s, h, d, dtype=jnp.float32, seed=56):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(b, s, h * d)).astype(np.float32) * 1.7, dtype)
        ct = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32), dtype)
        return x, ct

    # (B, S, H, d, theta, interpret): heads of a lane tile take the kernels
    # under the interpreter (one block and several a sequence, seven heads a
    # group as the cell's), anything else XLA's form
    @pytest.mark.parametrize("b,s,h,d,theta,interpret", [
        (2, 256, 3, 128, 10000.0, True), (1, 2048, 2, 128, 1.5e6, True),
        (2, 256, 7, 128, 1.5e6, True), (1, 2048, 2, 256, 500000.0, True),
        (2, 256, 3, 128, 10000.0, False), (2, 16, 7, 8, 1.5e6, False),
        (2, 16, 1, 8, 1.5e6, False), (1, 24, 2, 6, 10000.0, True),
    ])
    def test_matches_rope_partial_and_its_gradient(self, b, s, h, d, theta, interpret):
        """f32 in: the output (B, H, S, d) and dx (B, S, H·d) are
        ``rope_partial``'s at the whole head to 1e-6."""
        from byteps_tpu.ops import head_norm as hn

        assert hn._kernel_path(s, d, interpret) == (interpret and d % 128 == 0)
        x, ct = self._inputs(b, s, h, d)

        def run(f):
            y, pull = jax.vjp(f, x)
            return y, pull(ct)[0]

        got = run(lambda x: hn.head_rope(x, d, theta, interpret=interpret))
        want = run(lambda x: self._old(x, d, theta))
        assert got[0].shape == (b, h, s, d) and got[1].shape == x.shape
        for name, g, r in zip(("out", "dx"), got, want):
            assert g.shape == r.shape and g.dtype == r.dtype
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=0,
                                       atol=1e-6 * float(jnp.max(jnp.abs(r))), err_msg=name)

    @pytest.mark.parametrize("interpret", [True, False], ids=["kernels", "xla"])
    def test_bf16_in_is_f32_arithmetic_rounded_once(self, interpret):
        """bf16 in and out: the tables and the products are f32 and the
        result is rounded once, both ways — ``rope_partial``'s own, to the
        bit on XLA's form and to half a unit in the last place by the
        kernels."""
        from byteps_tpu.ops import head_norm as hn

        x, ct = self._inputs(1, 256, 2, 128, jnp.bfloat16)
        exact = self._old(x.astype(jnp.float32), 128, 1.5e6)
        y, pull = jax.vjp(lambda x: hn.head_rope(x, 128, 1.5e6, interpret=interpret), x)
        (dx,) = pull(ct)
        assert y.dtype == dx.dtype == jnp.bfloat16
        off = np.abs(np.asarray(y, np.float32) - np.asarray(exact))
        assert np.all(off <= np.abs(np.asarray(exact)) * 2.0 ** -8 + 1e-30)
        _, old_pull = jax.vjp(lambda x: self._old(x, 128, 1.5e6), x)
        old = np.asarray(old_pull(ct)[0], np.float32)
        assert np.all(np.abs(np.asarray(dx, np.float32) - old) <= np.abs(old) * 2.0 ** -7 + 1e-30)
        if not interpret:
            assert np.array_equal(np.asarray(y, np.float32),
                                  np.asarray(self._old(x, 128, 1.5e6), np.float32))

    def test_the_backward_pass_keeps_nothing(self):
        """The rotation's transpose is the rotation by the negative angle:
        the forward rule's residuals are empty, and turning forth then back
        gives x again."""
        from byteps_tpu.ops import head_norm as hn

        x, _ = self._inputs(1, 16, 2, 8)
        _, kept = hn._rope_fwd(x, 8, 1e4, False, False)
        assert kept is None
        back = hn._turned(hn._turned(x, 8, 1e4, False, False, False), 8, 1e4, True, False, False)
        np.testing.assert_allclose(back, x, atol=1e-5)

    @pytest.mark.parametrize("f,d", [(21, 7), (24, 16)], ids=["odd_head", "no_whole_heads"])
    def test_columns_that_are_no_paired_heads_are_refused(self, f, d):
        from byteps_tpu.ops import head_norm as hn

        with pytest.raises(ValueError, match="no heads of"):
            hn.head_rope(jnp.ones((1, 4, f)), d, 10000.0)

    def test_a_traced_call_is_counted_by_its_path(self, monkeypatch):
        """``head_rope_kernel_traces`` | ``head_rope_xla_traces``: one count a
        traced call, by what ``_kernel_path`` chose — a step that fell back to
        XLA's form can be told from the registry."""
        from byteps_tpu.core.telemetry import counters
        from byteps_tpu.ops import head_norm as hn

        def grown(run):
            before = counters().snapshot()
            run()
            after = counters().snapshot()
            return tuple(after.get(k, 0) - before.get(k, 0)
                         for k in ("head_rope_kernel_traces", "head_rope_xla_traces"))

        x, _ = self._inputs(1, 256, 2, 128)
        assert grown(lambda: hn.head_rope(x, 128, 1e4)) == (0, 1)  # no TPU here
        assert grown(lambda: hn.head_rope(x, 128, 1e4, interpret=True)) == (1, 0)
        step = jax.jit(jax.grad(lambda x: jnp.sum(hn.head_rope(x, 128, 1e4))))
        assert grown(lambda: (step(x), step(x))) == (0, 1)  # traced once, run twice
        monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
        small = jax.ShapeDtypeStruct((1, 256, 2 * 64), jnp.bfloat16)
        assert grown(lambda: jax.eval_shape(lambda x: hn.head_rope(x, 64, 1e4), small)) == (0, 1)

    def test_the_kernels_are_the_program_where_heads_tile(self, monkeypatch):
        """On a TPU at heads of a lane tile both passes are one Pallas call
        each — ``head_rope_fwd``, ``head_rope_bwd`` —, read from the traced
        gradient; at a head that does not tile, none (``_kernel_path``, the
        one chooser the normed pass has)."""
        from byteps_tpu.ops import head_norm as hn

        monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
        grad = jax.grad(lambda x: jnp.sum(hn.head_rope(x, 128, 1.5e6).astype(jnp.float32)))
        x = jax.ShapeDtypeStruct((2, 2048, 7 * 128), jnp.bfloat16)
        assert _kernel_names(grad, x) == [hn.ROPE_BWD_KERNEL, hn.ROPE_FWD_KERNEL]
        grad64 = jax.grad(lambda x: jnp.sum(hn.head_rope(x, 64, 1.5e6).astype(jnp.float32)))
        assert _kernel_names(grad64, jax.ShapeDtypeStruct((2, 2048, 7 * 64), jnp.bfloat16)) == []


class TestConvSilu:
    """``ops/causal_conv.conv_silu``: the depthwise causal convolution before a
    linear mixer with its bias, silu and a head's l2 norm, taken from a column
    range of a projection's output — the kernels (interpret mode) and XLA's
    form alike held to a token-by-token loop, forward and every gradient."""

    B, S, W, LO, HI, K = 2, 64, 640, 256, 512, 4
    #: four row blocks a sequence, one or two lane blocks a range, two chunks a block
    BLOCKS = (16, 128, 8)

    @classmethod
    def _inputs(cls, with_bias, dtype=jnp.float32, seed=64):
        rng = np.random.default_rng(seed)
        normal = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(np.float32))  # noqa: E731
        c = cls.HI - cls.LO
        return (normal(cls.B, cls.S, cls.W).astype(dtype), normal(cls.K, c) * 0.5,
                normal(c) if with_bias else None, normal(cls.B, cls.S, c).astype(dtype))

    @classmethod
    def _loop(cls, wide, taps, bias, l2_head, scale):
        """The definition, a token at a time, in f32."""
        x = wide[..., cls.LO:cls.HI].astype(jnp.float32)
        out = []
        for t in range(cls.S):
            u = sum(taps[j] * x[:, t - cls.K + 1 + j]
                    for j in range(cls.K) if t - cls.K + 1 + j >= 0)
            a = jax.nn.silu(u if bias is None else u + bias)
            if l2_head:
                heads = a.reshape(cls.B, -1, l2_head)
                norm = jnp.sqrt(jnp.sum(heads * heads, axis=-1, keepdims=True) + 1e-6)
                a = (heads / norm * scale).reshape(a.shape)
            out.append(a)
        return jnp.stack(out, axis=1)

    @classmethod
    @functools.cache
    def _loop_vjp(cls, with_bias, l2_head):
        """(the inputs, the loop's y, its pullback) at scale 0.3: the
        definition is differentiated once and read by every case on it."""
        wide, taps, bias, ct = cls._inputs(with_bias)
        args = (wide, taps) + ((bias,) if with_bias else ())
        y, pull = jax.vjp(lambda w, t, *b: cls._loop(w, t, *(b or (None,)), l2_head, 0.3), *args)
        return (wide, taps, bias, ct), y, pull

    @classmethod
    def _run(cls, wide, taps, bias, l2_head, scale, **how):
        from byteps_tpu.ops.causal_conv import conv_silu

        return conv_silu(wide, taps, bias, lo=cls.LO, hi=cls.HI, l2_head=l2_head, scale=scale,
                         **how)

    @pytest.mark.parametrize("interpret", [True, False], ids=["kernels", "xla"])
    @pytest.mark.parametrize("l2_head", [None, 128], ids=["plain", "l2_heads"])
    @pytest.mark.parametrize("with_bias", [False, True], ids=["no_bias", "bias"])
    def test_forward_and_every_gradient_are_the_token_loops(self, with_bias, l2_head, interpret):
        """f32: y, d wide (zero outside the columns, the halo's rows across
        every block edge and at the sequence's start), d taps and d bias
        against the loop's autodiff to 2e-6 of each one's largest."""
        from byteps_tpu.ops import causal_conv as cc

        (wide, taps, bias, ct), want_y, want_pull = self._loop_vjp(with_bias, l2_head)
        fit = cc._blocks(wide, taps, self.LO, self.HI, l2_head, 0.3, interpret, self.BLOCKS)
        assert (fit.rows, fit.lanes, fit.chunk, fit.halo) == (16, l2_head or 128, 8, 8)
        assert cc._kernel_path(fit, interpret) == interpret
        args = (wide, taps) + ((bias,) if with_bias else ())
        y, pull = jax.vjp(lambda w, t, *b: self._run(
            w, t, *(b or (None,)), l2_head, 0.3, interpret=interpret, blocks=self.BLOCKS), *args)
        got, want = (y, *pull(ct)), (want_y, *want_pull(ct))
        assert len(got) == 3 + with_bias and got[1].shape == wide.shape
        assert not np.any(np.asarray(got[1][..., :self.LO])) and not np.any(
            np.asarray(got[1][..., self.HI:]))
        for name, g, r in zip(("y", "dwide", "dtaps", "dbias"), got, want):
            assert g.shape == r.shape and g.dtype == r.dtype, name
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=0,
                                       atol=2e-6 * float(jnp.max(jnp.abs(r))), err_msg=name)

    # a cotangent on ONE token: the sequence's first, a block's last, a
    # block's first (its dx lies in the block before), the sequence's last
    @pytest.mark.parametrize("token", [0, 15, 16, 32, 63])
    def test_a_tokens_cotangent_reaches_the_taps_before_it_across_a_block_edge(self, token):
        (wide, taps, bias, ct), _, loop_pull = self._loop_vjp(True, 128)
        ct = jnp.zeros_like(ct).at[:, token].set(ct[:, token])
        got = np.asarray(jax.vjp(lambda w: self._run(
            w, taps, bias, 128, 0.3, interpret=True, blocks=self.BLOCKS), wide)[1](ct)[0])
        want = np.asarray(loop_pull(ct)[0])
        reached = np.flatnonzero(np.any(got != 0, axis=(0, 2)))
        assert list(reached) == list(range(max(token - self.K + 1, 0), token + 1))
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-6 * np.abs(want).max())

    @pytest.mark.parametrize("taps_k,kernels", [(3, True), (9, True), (10, False)])
    def test_any_tap_count_whose_reach_is_inside_a_sublane_tile(self, taps_k, kernels):
        """K taps reach K − 1 rows back, read from ONE f32 sublane tile before
        a chunk (8 rows): 3 and 9 taps take the kernels, 10 XLA's form —
        each equal to ``causal_conv`` + bias + silu by autodiff."""
        from byteps_tpu.models.moe_family import causal_conv
        from byteps_tpu.ops import causal_conv as cc

        wide, _, bias, ct = self._inputs(True)
        taps = jnp.asarray(np.random.default_rng(taps_k).normal(
            size=(taps_k, self.HI - self.LO)).astype(np.float32)) * 0.4
        fit = cc._blocks(wide, taps, self.LO, self.HI, None, 1.0, True, self.BLOCKS)
        assert (fit is not None) == kernels

        def both(f):
            y, pull = jax.vjp(f, wide, taps, bias)
            return (y, *pull(ct))

        got = both(lambda w, t, b: self._run(w, t, b, None, 1.0, interpret=True,
                                             blocks=self.BLOCKS))
        want = both(lambda w, t, b: jax.nn.silu(causal_conv(w[..., self.LO:self.HI], t) + b))
        for name, g, r in zip(("y", "dwide", "dtaps", "dbias"), got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=0,
                                       atol=2e-6 * float(jnp.max(jnp.abs(r))), err_msg=name)

    @pytest.mark.parametrize("interpret", [True, False], ids=["kernels", "xla"])
    def test_bf16_in_is_f32_arithmetic_rounded_once(self, interpret):
        """bf16 in and out: products, silu, the statistic and the sums over
        the tokens are f32, y and d wide are rounded ONCE — within half a unit
        in the last place of the loop's f32 result —, and the taps' and the
        bias' gradients stay f32 (what ``delta_moe._conv_rounded``'s test
        held of the pass this one replaced)."""
        wide, taps, bias, ct = self._inputs(True, jnp.bfloat16)

        def both(f, wide, ct):
            y, pull = jax.vjp(f, wide, taps, bias)
            return (y, *pull(ct))

        got = both(lambda w, t, b: self._run(w, t, b, 128, 0.3, interpret=interpret,
                                             blocks=(32, 128, 16)), wide, ct)
        exact = both(lambda w, t, b: self._loop(w, t, b, 128, 0.3),
                     wide.astype(jnp.float32), ct.astype(jnp.float32))
        assert [g.dtype for g in got] == [jnp.bfloat16, jnp.bfloat16, jnp.float32, jnp.float32]
        y, r = np.asarray(got[0], np.float32), np.asarray(exact[0])
        assert np.all(np.abs(y - r) <= np.abs(r) * 2.0 ** -8 + 1e-30)
        if interpret:  # autodiff's dx (XLA's form) adds the taps' rounded products in bf16
            dx, r = np.asarray(got[1], np.float32), np.asarray(exact[1])
            assert np.all(np.abs(dx - r) <= np.abs(r) * 2.0 ** -8 + 1e-6)
        for g, r in zip(got[2:], exact[2:]):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=0,
                                       atol=3e-6 * float(jnp.max(jnp.abs(r))))

    def test_the_backward_pass_keeps_the_projection_and_nothing_new(self):
        """The forward rule's residuals are ``wide``, the taps and the bias
        as they came in: no pre-activation, no statistic."""
        from byteps_tpu.ops import causal_conv as cc

        wide, taps, bias, _ = self._inputs(True)
        fit = cc._blocks(wide, taps, self.LO, self.HI, 128, 0.3, True, self.BLOCKS)
        _, kept = cc._fwd(wide, taps, bias, fit)
        assert [k is x for k, x in zip(kept, (wide, taps, bias))] == [True] * 3

    @pytest.mark.parametrize("lo,hi,l2_head", [(256, 700, None), (512, 256, None), (0, 256, 96)],
                             ids=["past_the_width", "empty", "no_whole_heads"])
    def test_ranges_that_are_no_columns_are_refused(self, lo, hi, l2_head):
        from byteps_tpu.ops.causal_conv import conv_silu

        with pytest.raises(ValueError, match="no such convolution"):
            conv_silu(jnp.ones((1, 16, 640)), jnp.ones((4, max(hi - lo, 1))), lo=lo, hi=hi,
                      l2_head=l2_head)

    def test_a_traced_call_is_counted_by_its_path(self, monkeypatch):
        """``conv_kernel_traces`` | ``conv_xla_traces``: one count a traced
        call, by what ``_kernel_path`` chose."""
        from byteps_tpu.core.telemetry import counters

        def grown(run):
            before = counters().snapshot()
            run()
            after = counters().snapshot()
            return tuple(after.get(k, 0) - before.get(k, 0)
                         for k in ("conv_kernel_traces", "conv_xla_traces"))

        wide, taps, bias, _ = self._inputs(True)
        assert grown(lambda: self._run(wide, taps, bias, None, 1.0)) == (0, 1)  # no TPU here
        assert grown(lambda: self._run(wide, taps, bias, None, 1.0, interpret=True)) == (1, 0)
        step = jax.jit(jax.grad(lambda w: jnp.sum(self._run(w, taps, bias, 128, 1.0))))
        assert grown(lambda: (step(wide), step(wide))) == (0, 1)  # traced once, run twice
        monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
        shape = jax.ShapeDtypeStruct(wide.shape, jnp.bfloat16)
        assert grown(lambda: jax.eval_shape(
            lambda w: self._run(w, taps, bias, 128, 1.0), shape)) == (1, 0)

    # (batch, sequence, the projection's width, lo, hi, a head, a bias): the
    # three families' ranges at their published widths, then what does not
    # tile — columns that start inside a lane tile, heads of 64, a sequence
    # that is no whole sublane tiles of bf16
    @pytest.mark.parametrize("b,s,width,lo,hi,l2_head,with_bias,kernels", [
        (1, 16384, 12288, 2048, 4096, 128, False, True),
        (2, 8192, 10304, 8192, 9216, None, True, True),
        (1, 16384, 10240, 0, 5120, None, True, True),
        (1, 16384, 12288, 64, 2112, None, False, False),
        (1, 16384, 12288, 0, 2048, 64, False, False),
        (1, 24, 12288, 0, 2048, 128, False, False),
    ], ids=["gated_delta_k", "state_space_B", "mamba1_x", "inside_a_tile", "heads_of_64",
            "ragged_sequence"])
    def test_the_kernels_are_the_program_where_the_columns_tile(
            self, monkeypatch, b, s, width, lo, hi, l2_head, with_bias, kernels):
        """On a TPU both passes are one Pallas call each, read from the traced
        gradient; where the shapes do not tile, none — ``_kernel_path`` alone
        chooses, from the platform and the shapes."""
        from byteps_tpu.ops import causal_conv as cc

        monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
        shapes = [jax.ShapeDtypeStruct((b, s, width), jnp.bfloat16),
                  jax.ShapeDtypeStruct((4, hi - lo), jnp.float32)]
        shapes += [jax.ShapeDtypeStruct((hi - lo,), jnp.float32)] * with_bias

        def loss(wide, taps, *bias):
            return jnp.sum(cc.conv_silu(wide, taps, *bias, lo=lo, hi=hi, l2_head=l2_head)
                           .astype(jnp.float32))

        names = _kernel_names(jax.grad(loss, argnums=tuple(range(len(shapes)))), *shapes)
        assert names == ([cc.CONV_BWD_KERNEL, cc.CONV_FWD_KERNEL] if kernels else [])


class TestOneBitDevice:
    # a block multiple; the engine's default partition (BYTEPS_PARTITION_BYTES
    # / 4, NOT a block multiple: padded on the device); a ragged tail
    @pytest.mark.parametrize("n", [32 * 1024 * 2, 1_024_000, 12_345])
    def test_wire_parity_with_host_codec(self, n):
        """Device-compressed sign words must be byte-identical to the host
        OneBitCompressor so the PS server decodes it unchanged — at every
        length, since the Pallas packer (here in the interpreter) pads to
        its block and trims.  The f32 scale (sum(|g|)/n) may differ by an
        ULP from the host codec's accumulation order, so it gets a float
        comparison rather than a byte one."""
        from byteps_tpu.compression.impl import OneBitCompressor

        rng = np.random.default_rng(3)
        g = rng.normal(size=n).astype(np.float32)
        scale, words = onebit_compress_device(jnp.asarray(g), scaling=True,
                                              interpret=True)
        dev_payload = onebit_payload(scale, words)
        host_payload = OneBitCompressor(n, scaling=True).compress(g)
        assert dev_payload[4:] == host_payload[4:]  # sign words: bit-exact
        np.testing.assert_allclose(
            np.frombuffer(dev_payload[:4], np.float32),
            np.frombuffer(host_payload[:4], np.float32),
            rtol=1e-6,
        )

    def test_roundtrip_on_device(self):
        rng = np.random.default_rng(4)
        g = rng.normal(size=4096).astype(np.float32)
        scale, words = onebit_compress_device(jnp.asarray(g), scaling=True)
        out = onebit_decompress_device(scale, words, g.size)
        np.testing.assert_array_equal(np.signbit(np.asarray(out)), np.signbit(g))
        np.testing.assert_allclose(np.abs(np.asarray(out)), np.abs(g).mean(), rtol=1e-5)

    def test_off_tpu_without_interpret_uses_jnp_path(self):
        g = np.ones(100, np.float32)
        scale, words = onebit_compress_device(jnp.asarray(g), scaling=False)
        assert words.shape == (4,)  # ceil(100/32)
        out = onebit_decompress_device(scale, words, 100)
        np.testing.assert_allclose(np.asarray(out), 1.0)

    def test_the_packer_is_chosen_at_the_seam(self, monkeypatch):
        """``onebit_compress_device`` asks ``_dispatch.kernels_run`` as every
        kernel module does: a TPU stood in at ``_dispatch.platform`` gives the
        Pallas packer without ``interpret``, the CPU the jnp one.  (A length no
        other test traces: the function is jitted, and a trace is kept.)"""
        def packers(n):
            grad = jax.ShapeDtypeStruct((n,), jnp.float32)
            return len(list(_pallas_calls(jax.make_jaxpr(onebit_compress_device)(grad).jaxpr)))

        assert packers(7_001) == 0
        monkeypatch.setattr(_dispatch, "platform", lambda: "tpu")
        assert packers(7_003) == 1


class TestFlashLse:
    @pytest.mark.parametrize("causal", [False, True])
    def test_lse_matches_dense(self, causal):
        rng = np.random.default_rng(5)
        q, k, v = (
            jnp.asarray(rng.normal(size=(1, 2, 64, 16)).astype(np.float32))
            for _ in range(3)
        )
        from byteps_tpu.ops.flash_attention import flash_attention_lse

        out, lse = flash_attention_lse(
            q, k, v, causal=causal, block_q=16, block_k=16, interpret=True
        )
        scale = 16 ** -0.5
        s = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if causal:
            mask = np.tril(np.ones((64, 64), bool))
            s = np.where(mask, s, -1e30)
        ref_lse = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) + s.max(-1)
        p = np.exp(s - s.max(-1, keepdims=True))
        ref = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), ref_lse, rtol=2e-4, atol=2e-5)

    # (causal, block_q, block_k, d_qk, d_v) at sequence 64
    @pytest.mark.parametrize("causal,bq,bk,d_qk,d_v", [
        (True, 16, 16, 8, 8), (False, 16, 16, 8, 8),
        (True, 32, 8, 24, 16), (False, 8, 32, 24, 16),
    ])
    def test_lse_cotangent_folds_into_backward(self, causal, bq, bk, d_qk, d_v):
        """grad through a function of BOTH outputs (out, lse), so with a
        non-zero lse cotangent, must match the dense autodiff reference —
        the dlse→delta fold, seen by the one backward kernel."""
        from byteps_tpu.ops.flash_attention import (
            _dense_reference_lse,
            flash_attention_lse,
        )

        rng = np.random.default_rng(6)
        q, k, v = (
            jnp.asarray(rng.normal(size=(1, 2, 64, d)).astype(np.float32))
            for d in (d_qk, d_qk, d_v)
        )

        def loss_flash(q, k, v):
            o, lse = flash_attention_lse(
                q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True
            )
            return jnp.sum(o**2) + jnp.sum(jnp.sin(lse))

        def loss_dense(q, k, v):
            o, lse = _dense_reference_lse(q, k, v, causal, d_qk ** -0.5)
            return jnp.sum(o**2) + jnp.sum(jnp.sin(lse))

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)


class TestDeviceCodecs:
    """On-device topk/dithering: wire parity with the
    host codecs and the D2H byte reduction that motivates them."""

    def test_topk_payload_bit_matches_host_codec(self):
        from byteps_tpu.compression.impl import TopKCompressor
        from byteps_tpu.ops.codecs_device import topk_compress_device, topk_payload

        rng = np.random.default_rng(0)
        n, k = 4096, 64
        grad = rng.normal(size=n).astype(np.float32)  # distinct |values| w.p. 1
        host = TopKCompressor(n, k).compress(grad)
        idx, vals = topk_compress_device(jnp.asarray(grad), k)
        assert topk_payload(idx, vals) == host

    def test_topk_tie_break_bit_matches_across_all_paths(self):
        """Equal |magnitudes| at the k-th boundary: every selector
        (native nth_element, numpy fallback, device lax.top_k) breaks
        ties toward the LOWER index, so the wire bytes are identical
        even on tie-heavy gradients — no 'unique k-th magnitude'
        caveat."""
        import byteps_tpu.compression.impl as impl
        from byteps_tpu.compression.impl import TopKCompressor
        from byteps_tpu.ops.codecs_device import (
            topk_compress_device,
            topk_payload,
        )

        rng = np.random.default_rng(7)
        n, k = 512, 32
        for _ in range(8):
            grad = rng.choice(
                [-2.0, -1.0, -0.5, 0.5, 1.0, 2.0], size=n
            ).astype(np.float32)
            codec = TopKCompressor(n, k)
            host = codec.compress(grad)
            real = impl.get_lib
            impl.get_lib = lambda: None  # force the numpy fallback
            try:
                fallback = codec.compress(grad)
            finally:
                impl.get_lib = real
            assert fallback == host
            idx, vals = topk_compress_device(jnp.asarray(grad), k)
            assert topk_payload(idx, vals) == host

    def test_topk_d2h_reduction_and_roundtrip(self):
        from byteps_tpu.compression.impl import TopKCompressor
        from byteps_tpu.ops.codecs_device import (
            topk_compress_device,
            topk_payload,
            topk_sum_device,
        )

        rng = np.random.default_rng(1)
        n, k = 8192, 128
        grad = rng.normal(size=n).astype(np.float32)
        idx, vals = topk_compress_device(jnp.asarray(grad), k)
        payload = topk_payload(idx, vals)
        # D2H bytes: 8k vs 4n — 32x smaller at this (n, k)
        assert len(payload) == 8 * k
        assert len(payload) * 32 == 4 * n
        # host server decodes the device payload exactly
        dec = TopKCompressor(n, k).decompress(payload, n)
        ref = topk_sum_device(idx, vals, n)
        np.testing.assert_array_equal(dec, np.asarray(ref))

    @pytest.mark.parametrize("natural,l2", [(False, False), (True, False),
                                            (False, True), (True, True)])
    def test_dithering_wire_decodes_identically_on_host(self, natural, l2):
        """Host DitheringCompressor.decompress of a DEVICE payload must
        equal the device decompress — exact decode parity (the wire carries
        levels; no RNG on the decode side)."""
        from byteps_tpu.compression.impl import DitheringCompressor
        from byteps_tpu.ops.codecs_device import (
            dithering_compress_device,
            dithering_decompress_device,
            dithering_payload,
        )

        rng = np.random.default_rng(2)
        n, s = 1024, 4
        grad = rng.normal(size=n).astype(np.float32)
        norm, levels = dithering_compress_device(
            jnp.asarray(grad), jax.random.PRNGKey(7), s=s, natural=natural, l2=l2
        )
        payload = dithering_payload(norm, levels)
        assert len(payload) == 4 + n  # ~4x smaller than 4n fp32
        host_codec = DitheringCompressor(
            n, k=s, partition="natural" if natural else "linear",
            normalize="l2" if l2 else "max",
        )
        host_dec = host_codec.decompress(payload, n)
        dev_dec = dithering_decompress_device(norm, levels, s=s, natural=natural)
        np.testing.assert_allclose(np.asarray(dev_dec), host_dec, rtol=1e-6)

    def test_dithering_unbiased_and_on_grid(self):
        """Stochastic rounding must be unbiased (E[decompress] = grad) and
        every level must sit on the host codec's quantization grid."""
        from byteps_tpu.ops.codecs_device import (
            dithering_compress_device,
            dithering_decompress_device,
        )

        rng = np.random.default_rng(3)
        n, s = 512, 4
        grad = rng.normal(size=n).astype(np.float32)
        acc = np.zeros(n, np.float64)
        trials = 200
        for t in range(trials):
            norm, levels = dithering_compress_device(
                jnp.asarray(grad), jax.random.PRNGKey(t), s=s
            )
            lv = np.asarray(levels, np.int32)
            assert np.all(np.abs(lv) <= s)
            acc += np.asarray(
                dithering_decompress_device(norm, levels, s=s), np.float64
            )
        mean = acc / trials
        # unbiasedness: mean of 200 draws within a few quantization-noise
        # standard errors of the input
        norm_v = float(np.abs(grad).max())
        se = norm_v / s / np.sqrt(trials)
        np.testing.assert_allclose(mean, grad, atol=6 * se)


class TestTunedBlocks:
    """tuned_blocks(): the on-chip sweep artifact (flash_blocks.json)
    feeds kernel block defaults; safe fallback when untuned."""

    @staticmethod
    def _module():
        # ops/__init__ re-exports the flash_attention FUNCTION, which
        # shadows the submodule in `import ... as` resolution
        import importlib

        return importlib.import_module("byteps_tpu.ops.flash_attention")

    def _patch_table(self, monkeypatch, tmp_path, doc):
        import json

        fa = self._module()

        path = tmp_path / "flash_blocks.json"
        path.write_text(json.dumps(doc))
        monkeypatch.setattr(fa, "_TUNED_PATH", str(path))
        return fa

    def test_default_when_untuned(self, monkeypatch, tmp_path):
        fa = self._module()

        monkeypatch.setattr(fa, "_TUNED_PATH", str(tmp_path / "absent.json"))
        assert fa.tuned_blocks(512) == (128, 128)

    def test_exact_and_nearest_below(self, monkeypatch, tmp_path):
        fa = self._patch_table(
            monkeypatch, tmp_path,
            {"blocks": {"512": [256, 128], "2048": [256, 512]}},
        )
        assert fa.tuned_blocks(512) == (256, 128)
        assert fa.tuned_blocks(1024) == (256, 128)  # nearest tuned below
        assert fa.tuned_blocks(4096) == (256, 512)
        assert fa.tuned_blocks(128) == (128, 128)   # nothing at/below

    def test_corrupt_table_falls_back(self, monkeypatch, tmp_path):
        fa = self._patch_table(monkeypatch, tmp_path, {"blocks": "nope"})
        assert fa.tuned_blocks(512) == (128, 128)

    def test_nondividing_entry_falls_back(self, monkeypatch, tmp_path):
        """A nearest-below entry whose blocks do not divide the requested
        seq must NOT be used (it would silently demote the kernel to the
        dense fallback); the safe default applies instead."""
        fa = self._patch_table(
            monkeypatch, tmp_path, {"blocks": {"512": [512, 512]}}
        )
        assert fa.tuned_blocks(768) == (128, 128)
        assert fa.tuned_blocks(1024) == (512, 512)

    def test_kernel_resolves_table_defaults(self, monkeypatch, tmp_path):
        """flash_attention with block_q/block_k=None resolves block sizes
        from the table: a distinctive (32, 32) entry must reach the Pallas
        kernel (spied via _flash, run in interpret mode so the kernel path
        executes off-TPU) and still match the dense reference."""
        import numpy as np

        fa = self._patch_table(
            monkeypatch, tmp_path, {"blocks": {"64": [32, 32]}}
        )
        seen = {}
        orig_flash = fa._flash

        def spy(q, k, v, causal, scale, bq, bk, interpret, window=None):
            seen["blocks"] = (bq, bk)
            return orig_flash(q, k, v, causal, scale, bq, bk, interpret, window)

        monkeypatch.setattr(fa, "_flash", spy)
        rng = np.random.default_rng(0)
        q, k, v = (rng.normal(size=(1, 2, 64, 16)).astype(np.float32)
                   for _ in range(3))
        import jax.numpy as jnp

        out = fa.flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True,
            interpret=True,
        )
        assert seen["blocks"] == (32, 32), "tuned table entry must be used"
        ref = fa._dense_reference(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), True, 16 ** -0.5
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# every kernel's body, held: what a refactor under byteps_tpu/ops/ must not move
# ---------------------------------------------------------------------------


def _ops_modules():
    import importlib
    import pkgutil

    import byteps_tpu.ops as ops

    return [importlib.import_module(f"byteps_tpu.ops.{m.name}")
            for m in pkgutil.iter_modules(ops.__path__)]


def _all_pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_pallas_eqns(sub)


def _flash_family(**kw):
    fa = TestFlashBand._fa()
    q, k = jnp.ones((1, 2, 128, 32), jnp.float32), jnp.ones((1, 1, 128, 32), jnp.float32)
    v = jnp.ones((1, 1, 128, 48), jnp.float32)
    return (lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, causal=True, block_q=64, block_k=32, interpret=True, **kw))), (q, k, v)


_banded_family = functools.partial(_flash_family, window=40)


def _block_diffusion_family():
    fa = TestFlashBand._fa()
    q, k = jnp.ones((1, 4, 32, 8), jnp.float32), jnp.ones((1, 2, 64, 8), jnp.float32)
    v = jnp.ones((1, 2, 64, 16), jnp.float32)
    return (lambda q, k, v: jnp.sum(fa.block_diffusion_attention(
        q, k, v, 4, block_q=16, block_k=16, interpret=True))), (q, k, v)


def _delta_family():
    from byteps_tpu.ops import gated_delta as gd

    q = jnp.ones((1, 128, 1, 128), jnp.float32)
    v, g = jnp.ones((1, 128, 2, 128), jnp.float32), -jnp.ones((1, 128, 2), jnp.float32)
    return (lambda q, k, v, g, beta: jnp.sum(gd.chunked_gated_delta_rule(
        q, k, v, g, beta, chunk=64, interpret=True))), (q, q, v, g, -g)


def _channel_delta_family():
    from byteps_tpu.ops import gated_delta as gd

    q = jnp.ones((1, 128, 2, 128), jnp.float32)
    return (lambda q, k, v, g, beta: jnp.sum(gd.chunked_gated_delta_rule(
        q, k, v, g, beta, chunk=64, interpret=True))), (q, q, q, -q, q[..., 0])


def _ssd_family():
    from byteps_tpu.ops import ssd

    x, b = jnp.ones((1, 256, 2 * 64), jnp.float32), jnp.ones((1, 256, 128), jnp.float32)
    dt, a = jnp.ones((1, 256, 2), jnp.float32), -jnp.ones((2,), jnp.float32)
    return (lambda x, dt, a, b, c: jnp.sum(ssd.ssd_scan(
        x, dt, a, b, c, 2, 1, interpret=True))), (x, dt, a, b, b)


def _head_norm_family():
    from byteps_tpu.ops.head_norm import head_norm_rope

    x, w = jnp.ones((1, 2, 16, 128), jnp.bfloat16), jnp.ones((128,), jnp.float32)
    return (lambda x, w: jnp.sum(head_norm_rope(x, w, 1e-6, 1e4, interpret=True)
                                 .astype(jnp.float32))), (x, w)


def _head_rope_family():
    from byteps_tpu.ops.head_norm import head_rope

    x = jnp.ones((1, 16, 2 * 128), jnp.bfloat16)
    return (lambda x: jnp.sum(head_rope(x, 128, 1e4, interpret=True).astype(jnp.float32))), (x,)


def _conv_silu_family():
    from byteps_tpu.ops.causal_conv import conv_silu

    wide, taps = jnp.ones((1, 64, 3 * 128), jnp.bfloat16), jnp.ones((4, 256), jnp.float32)
    return (lambda wide, taps, bias: jnp.sum(conv_silu(
        wide, taps, bias, lo=128, hi=384, l2_head=128, scale=0.5, interpret=True,
        blocks=(32, 128, 16)).astype(jnp.float32))), (wide, taps, taps[0])


def _mla_heads_family():
    from byteps_tpu.ops.mla_heads import mla_heads

    wide, rope = jnp.ones((1, 16, 2 * 128), jnp.bfloat16), jnp.ones((1, 16, 2 * 64), jnp.bfloat16)
    key = jnp.ones((1, 16, 64), jnp.bfloat16)
    return (lambda *xs: sum(jnp.sum(y.astype(jnp.float32)) for y in mla_heads(
        *xs, 2, 1e4, interpret=True))), (wide, rope, wide, wide, key)


#: kernel name → (how its public function is traced, sha256 of the
#: ``pallas_call`` equation that carries the name: grid, every block's shape
#: and index map, the compiler's parameters and the kernel's inner jaxpr —
#: scratch shapes are its arguments' —, printed without source info).  Taken at
#: 5708eba, before PR 61 moved a line under ``byteps_tpu/ops/``: a refactor of
#: a kernel keeps its digest, a change that means to move a kernel re-takes
#: that kernel's and says so.  Tracing alone; nothing runs.
FROZEN_KERNELS = {
    "flash_fwd": (_flash_family, "17f4984afcbb30d5"),
    "flash_bwd": (_flash_family, "dea075dbecfc2238"),
    "flash_fwd_win": (_banded_family, "751d10e1f1c0fbe9"),
    "flash_bwd_win": (_banded_family, "06380e8f4f614eda"),
    "flash_fwd_bd": (_block_diffusion_family, "0e5913f639e34235"),
    "flash_bwd_bd": (_block_diffusion_family, "aaa3ccccd3238e29"),
    "gdn_chunk_inverse": (_delta_family, "d774a47cbde0deb6"),
    "gdn_scan_fwd": (_delta_family, "fda07b824fb7a6f1"),
    "gdn_scan_bwd": (_delta_family, "9d43e1a5acde0992"),
    # taken when ISSUE 69 wrote the three kernels
    "kda_chunk_inverse": (_channel_delta_family, "7cd1ca8e672166cd"),
    "kda_scan_fwd": (_channel_delta_family, "fa133d8202b56221"),
    "kda_scan_bwd": (_channel_delta_family, "35defe9fa6f48384"),
    # taken when ISSUE 62 wrote the two kernels
    "ssd_scan_fwd": (_ssd_family, "8a92173852f35429"),
    "ssd_scan_bwd": (_ssd_family, "b8f29c716357b833"),
    "head_norm_fwd": (_head_norm_family, "4ac4c6ccddd05b64"),
    "head_norm_bwd": (_head_norm_family, "5c72e95db22fa069"),
    "head_rope_fwd": (_head_rope_family, "d5d946f207dc99c3"),
    "head_rope_bwd": (_head_rope_family, "1e07dd621526ddea"),
    "mla_heads_fwd": (_mla_heads_family, "509e04eb94d7e3c1"),
    "mla_heads_bwd": (_mla_heads_family, "64c84232f3393545"),
    # taken when ISSUE 64 wrote the two kernels
    "conv_silu_fwd": (_conv_silu_family, "868f93399d84f57d"),
    "conv_silu_bwd": (_conv_silu_family, "25892f57a3c9a72b"),
}


@functools.cache
def _traced_kernels(family):
    """{kernel name: digest} of a family's function and its gradient."""
    import hashlib

    fn, args = family()
    jaxpr = jax.make_jaxpr(jax.value_and_grad(fn, argnums=tuple(range(len(args)))))(*args).jaxpr
    found = {}
    for eqn in _all_pallas_eqns(jaxpr):
        grid = eqn.params["grid_mapping"]
        text = "\n".join([
            f"grid {grid.grid} index operands {grid.num_index_operands}",
            *(f"block {b.block_shape} of {b.array_aval.shape} at {b.index_map_jaxpr}"
              for b in grid.block_mappings),
            str(eqn.params["compiler_params"]), f"out {eqn.params['out_avals']}",
            eqn.params["jaxpr"].pretty_print(source_info=False, name_stack=False)])
        assert eqn.params["name"] not in found, "a kernel traced twice: two digests for one name"
        found[eqn.params["name"]] = hashlib.sha256(text.encode()).hexdigest()[:16]
    return found


@pytest.mark.parametrize("name", sorted(FROZEN_KERNELS))
def test_a_kernels_body_grid_and_blocks_stand(name):
    named = {value for module in _ops_modules() for key, value in vars(module).items()
             if key.endswith("_KERNEL")}
    assert named == set(FROZEN_KERNELS), "every *_KERNEL name under byteps_tpu/ops/ is held here"
    family, digest = FROZEN_KERNELS[name]
    assert _traced_kernels(family)[name] == digest
