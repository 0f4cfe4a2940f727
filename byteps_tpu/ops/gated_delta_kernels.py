"""The chunked gated delta rule as Pallas TPU kernels: everything a chunk
needs lives in VMEM from its first product to its last.

Three kernels, the mathematics of ``ops/gated_delta.py``'s docstring:

``gdn_chunk_inverse`` — inside a chunk, no state.  Grid (key heads, blocks of
chunks), every cell independent.  From k, g and β read once: γ, the decay
matrix, ``K Kᵀ`` (shared by the value heads of a key head), ``A`` and
``T = (I + A)⁻¹`` by the block rounds of ``gated_delta._inverse_by_blocks``,
their products at f32 accuracy on matrices that stay in VMEM.  Chunks are
stacked to the MXU's 128 rows (two chunks of 64 form one block-diagonal
128 × 128 matrix: a round costs the array the same and serves both).  Writes T.

``gdn_scan_fwd`` — along the sequence.  Grid (key heads [parallel], blocks of
chunks [sequential]); the d_k × d_v f32 states of a key head's value heads
stay in VMEM scratch across the chunk axis.  A chunk:
``U = T β (V − e^γ ∘ K S)``, ``o = e^γ ∘ (Q S) + (D ∘ Q Kᵀ) U``,
``S ← e^{γ_C} S + Kᵀ (e^{γ_C − γ} ∘ U)``.  Writes o and, where the rule is
differentiated, the chunk's entering state in the compute dtype.

``gdn_scan_bwd`` — the same walk from the last chunk to the first carrying
dS, a chunk's forward rebuilt from T and its entering state; the inverse's
rule ``dA = −Tᵀ dT Tᵀ`` at f32 accuracy; gradients for q, k (summed over
their value heads), v, g and β.

The ``custom_vjp``'s forward rule gives T, the entering states and o the names
in ``SAVED``, so a caller that rebuilds its layer in the backward pass
(``jax.checkpoint``) can keep them by a policy and run both forward kernels
once — ``models/delta_moe._layer_parts`` does, as the attention parts keep the
flash kernel's output and row statistics.  That pays where the layer is NOT
the body of a ``lax.scan``: there a kept array is copied into the scan's stack
and out again, and for the entering states and o the copies cost more than
``gdn_scan_fwd`` (measured: PERF.md §6, PRs 58 and 60).

The operands are token-major, as the projections around the rule write and
read them: q, k ``(B, S, H_k·d_k)``, v, o and their cotangents
``(B, S, H_v·d_v)``, heads side by side along the lanes.  Grid cell ``(i, j)``
finds key head ``i % H_k`` of batch ``i // H_k`` by its index map — a
``(chunks·C, d_k)`` block at column block ``i % H_k``, and the head's ``r``
value heads as one block ``r·d_v`` lanes wide — so no head-major copy of any
of them exists.  g, β, T and the entering states are the kernels' own: value
heads down the rows, ``(B·H_v, …)``.

γ, D, T and the states are f32; every other product takes its operands in the
compute dtype and accumulates in f32.  Per-token scalars arrive as rows
(chunk positions along the lanes) and are turned into columns by a masked
sum: no transposes, no lane-offset slices but the one that splits a stacked T.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from byteps_tpu.ops._chunk import (EXACT, F32, NN, NT, TN, by_head, column, decays, dot,
                                   inverse_rounds, iotas, round_levels, row, total)
from byteps_tpu.ops._dispatch import vma_union

#: the kernels' names: a trace files their time under these (none starts
#: with ``flash_``: the benchmark's readers take such calls for flash kernels)
INVERSE_KERNEL, FWD_KERNEL, BWD_KERNEL = "gdn_chunk_inverse", "gdn_scan_fwd", "gdn_scan_bwd"

#: the triangular inverse, the chunks' entering states and o carry these names
#: wherever the rule is differentiated: a ``jax.checkpoint`` whose policy saves
#: them (``save_only_these_names(*SAVED)``) runs neither ``gdn_chunk_inverse``
#: nor ``gdn_scan_fwd`` again in its backward pass, as ``flash_attention.SAVED``
SAVED = ("gdn_inverse", "gdn_entering", "gdn_out")

#: rows of the MXU: chunks are stacked to this many for the inverse
STACK = 128


# ---------------------------------------------------------------------------
# inside a chunk: T = (I + A)⁻¹
# ---------------------------------------------------------------------------


def _inverse_kernel(chunk, w, groups, r):
    """``w`` rows hold ``w // chunk`` chunks: one block-diagonal w × w matrix."""
    from jax.experimental import pallas as pl

    per = w // chunk

    def kernel(k_ref, g_ref, b_ref, t_ref):
        rows, cols = iotas(w)
        same = (rows // chunk) == (cols // chunk)
        seen, strict, eye = same & (rows >= cols), same & (rows > cols), rows == cols
        level = round_levels(rows, cols, strict, chunk)

        def group(i, carry):
            k = k_ref[0, pl.ds(pl.multiple_of(i * w, w), w), :]
            kk = dot(k, k, NT)
            for h in range(r):
                _, decay = decays(g_ref[h, i], seen, eye)
                inv = inverse_rounds(column(b_ref[h, i], eye) * decay * kk, level, eye, chunk)
                for j in range(per):
                    t_ref[h, i * per + j] = inv[j * chunk:(j + 1) * chunk,
                                                j * chunk:(j + 1) * chunk]
            return carry

        lax.fori_loop(0, groups, group, 0)

    return kernel


def _chunk_inverse(k, g, beta, hk, chunk, nb, interpret):
    """k (B, S, H_k·d_k), g and beta (BH_v, S) f32 → T (BH_v, N, C, C) f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, width = k.shape
    bhk, bhv, dk = b * hk, g.shape[0], width // hk
    r, n = bhv // bhk, s // chunk
    w = max(chunk, STACK)
    groups = nb * chunk // w
    stacked = lambda x: x.reshape(bhv, s // w, 1, w)  # noqa: E731
    scalars = pl.BlockSpec((r, groups, 1, w), lambda i, j: (i, j, 0, 0))
    return pl.pallas_call(
        _inverse_kernel(chunk, w, groups, r),
        out_shape=jax.ShapeDtypeStruct((bhv, n, chunk, chunk), F32, vma=vma_union(k, g, beta)),
        grid=(bhk, n // nb),
        in_specs=[pl.BlockSpec((1, nb * chunk, dk), lambda i, j: (i // hk, j, i % hk)),
                  scalars, scalars],
        out_specs=pl.BlockSpec((r, nb, chunk, chunk), lambda i, j: (i, j, 0, 0)),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=INVERSE_KERNEL,
    )(k, stacked(g), stacked(beta))


# ---------------------------------------------------------------------------
# along the sequence, forward
# ---------------------------------------------------------------------------


def _chunk_forward(q, k, v, t, g_row, b_row, state, masks, cdt):
    """One chunk of one value head from its entering state (f32).  Returns
    what the backward pass shares with it."""
    seen, eye = masks
    gam_col, decay = decays(g_row, seen, eye)
    gam_end = jnp.sum(g_row, axis=1, keepdims=True)  # (1, 1)
    e_gamma, to_end = jnp.exp(gam_col), jnp.exp(gam_end - gam_col)
    held = state.astype(cdt)
    ks, qs = dot(k, held, NN), dot(q, held, NN)
    rhs = (v.astype(F32) - e_gamma * ks).astype(cdt)
    t_beta = (t * b_row).astype(cdt)  # the row scales go on T's columns
    u = dot(t_beta, rhs, NN)
    return dict(decay=decay, e_gamma=e_gamma, to_end=to_end, last=jnp.exp(gam_end), held=held,
                ks=ks, qs=qs, rhs=rhs, t_beta=t_beta, u=u, u_cdt=u.astype(cdt),
                u_to_end=(to_end * u).astype(cdt))


def _fwd_kernel(chunk, nb, r, cdt, save):
    from jax.experimental import pallas as pl

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, o_ref, *rest):
        entering_ref, state = rest if save else (None, rest[0])
        dv = v_ref.shape[-1] // r  # value head h of the key head: lanes h·d_v …
        rows, cols = iotas(chunk)
        masks = (rows >= cols, rows == cols)

        @pl.when(pl.program_id(1) == 0)
        def _start():
            state[...] = jnp.zeros_like(state)

        def one(c, carry):
            at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            q, k = q_ref[0, at, :], k_ref[0, at, :]
            qk = dot(q, k, NT)
            for h in range(r):
                lanes = slice(h * dv, (h + 1) * dv)
                f = _chunk_forward(q, k, v_ref[0, at, lanes], t_ref[h, c], g_ref[h, c],
                                   b_ref[h, c], state[h], masks, cdt)
                if save:
                    entering_ref[h, c] = f["held"]
                o_ref[0, at, lanes] = f["e_gamma"] * f["qs"] + dot(
                    (f["decay"] * qk).astype(cdt), f["u_cdt"], NN)
                state[h] = f["last"] * state[h] + dot(k, f["u_to_end"], TN)
            return carry

        lax.fori_loop(0, nb, one, 0)

    return kernel


def _specs(hk, r, nb, chunk, dk, dv, index):
    """Block specs of a (batch · key heads, blocks of chunks) grid: q | k and
    v | o | do token-major (cell i is key head i % hk of batch i // hk, its r
    value heads side by side), g | β, T and the entering states by value head."""
    from jax.experimental import pallas as pl

    return dict(
        qk=pl.BlockSpec((1, nb * chunk, dk), lambda i, j: (i // hk, index(j), i % hk)),
        v=pl.BlockSpec((1, nb * chunk, r * dv), lambda i, j: (i // hk, index(j), i % hk)),
        scalar=pl.BlockSpec((r, nb, 1, chunk), lambda i, j: (i, index(j), 0, 0)),
        t=pl.BlockSpec((r, nb, chunk, chunk), lambda i, j: (i, index(j), 0, 0)),
        state=pl.BlockSpec((r, nb, dk, dv), lambda i, j: (i, index(j), 0, 0)),
    )


def _dims(q, v, g, hk):
    """(BH_k, BH_v, r, d_k, d_v) of token-major q (B, S, H_k·d_k) and v
    (B, S, H_v·d_v) beside g (BH_v, S)."""
    b, bhv = q.shape[0], g.shape[0]
    return b * hk, bhv, bhv // (b * hk), q.shape[-1] // hk, v.shape[-1] // (bhv // b)


def _scan_forward(q, k, v, g, beta, t, hk, chunk, nb, save, interpret):
    """→ o (B, S, H_v·d_v) f32 and, if ``save``, every chunk's entering state
    (BH_v, N, d_k, d_v) in the compute dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bhk, bhv, r, dk, dv = _dims(q, v, g, hk)
    s = q.shape[1]
    n, cdt = s // chunk, q.dtype
    vma = vma_union(q, k, v, g, beta, t)
    spec = _specs(hk, r, nb, chunk, dk, dv, lambda j: j)
    by_chunk = lambda x: x.reshape(bhv, n, 1, chunk)  # noqa: E731
    out_shape = [jax.ShapeDtypeStruct(v.shape, F32, vma=vma)]
    out_specs = [spec["v"]]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((bhv, n, dk, dv), cdt, vma=vma))
        out_specs.append(spec["state"])
    out = pl.pallas_call(
        _fwd_kernel(chunk, nb, r, cdt, save),
        out_shape=out_shape,
        grid=(bhk, n // nb),
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["scalar"], spec["scalar"], spec["t"]],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((r, dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=FWD_KERNEL,
    )(q, k, v, by_chunk(g), by_chunk(beta), t)
    return tuple(out) if save else (out[0], None)


# ---------------------------------------------------------------------------
# along the sequence, backward
# ---------------------------------------------------------------------------


def _bwd_kernel(chunk, nb, r, cdt):
    from jax.experimental import pallas as pl

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, entering_ref, do_ref,
               dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate):
        rows, cols = iotas(chunk)
        seen, strict, eye = rows >= cols, rows > cols, rows == cols
        is_last = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
        dv = v_ref.shape[-1] // r

        @pl.when(pl.program_id(1) == 0)
        def _start():
            dstate[...] = jnp.zeros_like(dstate)

        def one(step, carry):
            c = nb - 1 - step
            at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            q, k = q_ref[0, at, :], k_ref[0, at, :]
            qk, kk = dot(q, k, NT), dot(k, k, NT)
            dq = jnp.zeros(q.shape, F32)
            dk = jnp.zeros(k.shape, F32)
            dqk = jnp.zeros((chunk, chunk), F32)  # summed over the value heads
            dkk = jnp.zeros((chunk, chunk), F32)
            for h in range(r):
                t, b_row = t_ref[h, c], b_ref[h, c]
                held = entering_ref[h, c]
                lanes = slice(h * dv, (h + 1) * dv)
                f = _chunk_forward(q, k, v_ref[0, at, lanes], t, g_ref[h, c], b_row,
                                   held, (seen, eye), cdt)
                decay, e_gamma, to_end = f["decay"], f["e_gamma"], f["to_end"]
                b_col = column(b_row, eye)
                do = do_ref[0, at, lanes]
                leaving = dstate[h]  # the cotangent of the state this chunk leaves
                leaving_cdt = leaving.astype(cdt)

                k_dstate = dot(k, leaving_cdt, NN)  # cotangent of e^{γ_C − γ} ∘ U
                du = (dot((decay * qk).astype(cdt), do.astype(cdt), TN)
                      + to_end * k_dstate).astype(cdt)
                dp = dot(do.astype(cdt), f["u_cdt"], NT)
                dqs = (e_gamma * do).astype(cdt)
                drhs = dot(f["t_beta"], du, TN)
                dt_beta = dot(du, f["rhs"], NT)
                dks = (-e_gamma * drhs).astype(cdt)
                dq = dq + dot(dqs, held, NT)
                dk = dk + dot(dks, held, NT) + dot(f["u_to_end"], leaving_cdt, NT)
                dv_ref[0, at, lanes] = drhs.astype(dv_ref.dtype)
                dstate[h] = f["last"] * leaving + dot(q, dqs, TN) + dot(k, dks, TN)

                # T = (I + A)⁻¹: dA = −Tᵀ dT Tᵀ, strictly lower
                da = jnp.where(strict, -dot(dot(t, dt_beta * b_row, TN, EXACT),
                                             t, NT, EXACT), 0.0)
                dqk = dqk + decay * dp
                dkk = dkk + da * b_col * decay
                # the exponents γ_i − γ_j of D, through P = D ∘ Q Kᵀ and A = β D ∘ K Kᵀ
                dexp = (dp * qk + da * b_col * kk) * decay
                d_e_gamma = jnp.sum(do * f["qs"] - drhs * f["ks"], axis=1, keepdims=True)
                d_to_end = jnp.sum(f["u"] * k_dstate, axis=1, keepdims=True) * to_end
                d_last = total(held.astype(F32) * leaving) * f["last"]
                dgam = (jnp.sum(dexp, axis=1, keepdims=True) + d_e_gamma * e_gamma - d_to_end
                        - column(jnp.sum(dexp, axis=0, keepdims=True), eye))
                dgam = dgam + jnp.where(is_last, total(d_to_end) + d_last, 0.0)
                # γ is g's running sum: dg_m = Σ_{i ≥ m} dγ_i, as a row
                dg_ref[h, c] = jnp.sum(jnp.where(seen, dgam, 0.0), axis=0, keepdims=True)
                db_ref[h, c] = (jnp.sum(dt_beta * t, axis=0, keepdims=True)
                                + row(jnp.sum(da * decay * kk, axis=1, keepdims=True), eye))
            dqk, dkk = dqk.astype(cdt), dkk.astype(cdt)
            dq_ref[0, at, :] = (dq + dot(dqk, k, NN)).astype(dq_ref.dtype)
            dk_ref[0, at, :] = (dk + dot(dqk, q, TN) + dot(dkk, k, NN)
                                + dot(dkk, k, TN)).astype(dk_ref.dtype)
            return carry

        lax.fori_loop(0, nb, one, 0)

    return kernel


def _scan_backward(q, k, v, g, beta, t, entering, do, hk, chunk, nb, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bhk, bhv, r, dk, dv = _dims(q, v, g, hk)
    s = q.shape[1]
    n, cdt = s // chunk, q.dtype
    vma = vma_union(q, k, v, g, beta, t, entering, do)
    last = n // nb - 1
    # from the last block to the first
    spec = _specs(hk, r, nb, chunk, dk, dv, lambda j: last - j)
    by_chunk = lambda x: x.reshape(bhv, n, 1, chunk)  # noqa: E731
    shape = lambda x, dtype=None: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, dtype or x.dtype, vma=vma)
    dq, dk_, dv_, dg, db = pl.pallas_call(
        _bwd_kernel(chunk, nb, r, cdt),
        out_shape=[shape(q), shape(k), shape(v), shape(by_chunk(g), F32),
                   shape(by_chunk(beta), F32)],
        grid=(bhk, n // nb),
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["scalar"], spec["scalar"], spec["t"],
                  spec["state"], spec["v"]],
        out_specs=[spec["qk"], spec["qk"], spec["v"], spec["scalar"], spec["scalar"]],
        scratch_shapes=[pltpu.VMEM((r, dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=BWD_KERNEL,
    )(q, k, v, by_chunk(g), by_chunk(beta), t, entering, do)
    return dq, dk_, dv_, dg.reshape(g.shape).astype(g.dtype), db.reshape(beta.shape).astype(
        beta.dtype)


# ---------------------------------------------------------------------------
# the rule with its backward pass
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _rule(q, k, v, g, beta, hk, chunk, blocks, interpret):
    t = _chunk_inverse(k, g, beta, hk, chunk, blocks[0], interpret)
    return _scan_forward(q, k, v, g, beta, t, hk, chunk, blocks[1], False, interpret)[0]


def _rule_fwd(q, k, v, g, beta, hk, chunk, blocks, interpret):
    # T takes its name before the walk reads it: a rebuilt walk reads the array
    # it was traced with, and an unnamed one brings the inverse back to feed it
    t = checkpoint_name(_chunk_inverse(k, g, beta, hk, chunk, blocks[0], interpret), SAVED[0])
    o, entering = _scan_forward(q, k, v, g, beta, t, hk, chunk, blocks[1], True, interpret)
    entering, o = checkpoint_name(entering, SAVED[1]), checkpoint_name(o, SAVED[2])
    return o, (q, k, v, g, beta, t, entering)


def _rule_bwd(hk, chunk, blocks, interpret, res, do):
    return _scan_backward(*res, do, hk, chunk, blocks[2], interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_kernels(q, k, v, g, beta, chunk, blocks, interpret=False):
    """q, k (B, S, H_k, d_k) and v (B, S, H_v, d_v) in the compute dtype, g
    and beta (B, S, H_v) f32; ``blocks`` = chunks a grid step of the three
    kernels (each divides S / chunk; the first is a whole number of stacks).
    Returns o (B, S, H_v, d_v) f32.  Differentiable in all five."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    o = _rule(q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk), v.reshape(b, s, hv * dv),
              by_head(g), by_head(beta), hk, chunk, tuple(blocks), interpret)
    return o.reshape(b, s, hv, dv)
