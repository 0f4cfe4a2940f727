"""The chunked delta rule with a decay a KEY CHANNEL (Kimi Delta Attention's;
``ops/gated_delta.py``'s docstring has the mathematics) as Pallas TPU kernels:
a chunk of one head lives in VMEM from Γ to o, the d_k × d_v f32 state in
VMEM scratch along the sequence — the design of ``ops/gated_delta_kernels.py``
for a decay a head, with the one thing that differs done inside the chunk:
``A_ij = Σ_c k_ic k_jc e^{Γ_ic − Γ_jc}`` (and B with q) does not factor into a
decay matrix times ``K Kᵀ``, so a chunk's ``SUB``-row sub-blocks are built in
VMEM (:func:`_grams`) — below the diagonal a product about a reference row
(the last row BEFORE the sub-block's first: rows fall from it, it lies below
every earlier column), on the diagonal the sum pair by pair over i ≥ j
alone.  Every exponent that is taken is ≤ 0.

``kda_chunk_inverse`` — inside a chunk, no state.  Grid (B·H, blocks of
chunks), every cell independent.  From k, g and β read once: Γ (the chunk's
running sum of g: a product with the lower-triangular ones matrix at f32
accuracy), A by sub-blocks, ``T = (I + β A)⁻¹`` by ``_chunk.inverse_rounds``
(the scalar kernel's rounds), chunks stacked to the MXU's 128 rows.  Writes T.

``kda_scan_fwd`` — along the sequence.  Grid (B·H [parallel], blocks of chunks
[sequential]).  ``U = T β (V − (e^Γ ⊙ K) S)``, ``o = (e^Γ ⊙ Q) S + B U``,
``S ← Diag(e^{Γ_C}) S + (e^{Γ_C − Γ} ⊙ K)ᵀ U``.  Writes o (f32) and, where the
rule is differentiated, a chunk's entering state in the compute dtype.

``kda_scan_bwd`` — the walk from the last chunk carrying dS, a chunk's forward
rebuilt from T and its entering state, ``dA = −Tᵀ dT Tᵀ`` at f32 accuracy;
gradients for q, k, v, β and g a channel: with R(M, X)_i = Σ_{j≤i} M_ij X_j ⊙
e^{Γ_i − Γ_j} and C(M, X)_j = Σ_{i≥j} M_ij X_i ⊙ e^{Γ_i − Γ_j} (built by
sub-blocks as A and B were: :func:`_gram_cotangents`), dK = R(dA, K) +
C(dA, K) + C(dB, Q), dQ = R(dB, K) and dΓ = K ⊙ (R(dA, K) − C(dA, K) −
C(dB, Q)) + Q ⊙ dQ, beside the cotangents through ``e^Γ``, ``e^{Γ_C − Γ}`` and
``e^{Γ_C}``; dg is dΓ's reverse running sum inside the chunk.

The ``custom_vjp``'s forward rule gives T, the entering states and o the names
in ``SAVED`` (as ``gated_delta_kernels.SAVED``): a caller that rebuilds its
layer in the backward pass keeps them by a policy and runs neither forward
kernel again (``models/channel_delta_moe._hidden``).  Each ``pallas_call`` is
under a ``jax.jit`` of its own: a step's layers share one traced body and one
lowered function a kernel.

Operands token-major, a head found by the index map: q, k, g ``(B, S, H·d_k)``
(g f32), v, o and their cotangents ``(B, S, H·d_v)``; β, T and the entering
states are the kernels' own, ``(B·H, …)``.  Γ, every exponential, T and S are
f32; ``k ⊙ e^{…}`` and ``q ⊙ e^{…}`` are formed in f32 and rounded to the compute
dtype only as MXU operands; the pairwise sums are f32 throughout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from byteps_tpu.ops._chunk import (EXACT, F32, NN, NT, TN, by_head, column, dot, inverse_rounds,
                                   iotas, round_levels, row)
from byteps_tpu.ops._dispatch import vma_union

#: the kernels' names: a trace files their time under these (none starts
#: with ``flash_``: the benchmark's readers take such calls for flash kernels)
INVERSE_KERNEL, FWD_KERNEL, BWD_KERNEL = "kda_chunk_inverse", "kda_scan_fwd", "kda_scan_bwd"

#: the ``checkpoint_name`` of the triangular inverse, the chunks' entering
#: states and o wherever the rule is differentiated
SAVED = ("gdn_channel_inverse", "gdn_channel_entering", "gdn_channel_out")

#: rows of the MXU: chunks are stacked to this many for the inverse
STACK = 128
#: rows of a sub-block: two sublane tiles of f32, one of bf16
SUB = 16


# ---------------------------------------------------------------------------
# a stack of chunks in VMEM: Γ, and the decayed Grams by sub-blocks
# ---------------------------------------------------------------------------


class _Stack:
    """The masks of ``w`` rows that hold ``w // chunk`` chunks of ``w // SUB``
    sub-blocks, and the reads that take a ROW of Γ | K for every sub-block at
    once (``gam_ref`` | ``k_ref``: (w, d_k) f32 VMEM scratch the kernel fills
    a stack at a time)."""

    def __init__(self, w, chunk, gam_ref, k_ref):
        self.w, self.chunk, self.m = w, chunk, w // SUB
        self.gam_ref, self.k_ref = gam_ref, k_ref
        rows, cols = iotas(w)
        same = (rows // chunk) == (cols // chunk)
        self.rows, self.cols = rows, cols
        self.seen, self.strict, self.eye = same & (rows >= cols), same & (rows > cols), rows == cols
        self.token = lax.broadcasted_iota(jnp.int32, (w, 1), 0)
        #: the column of a row's sub-block's first row
        self.first = (rows // SUB) * SUB
        #: sub-blocks with earlier sub-blocks in their chunk, each with the
        #: first row of its chunk
        self.lower = [(i, (i * SUB // chunk) * chunk) for i in range(self.m) if (i * SUB) % chunk]

    def running(self, g):
        """Γ: a chunk's running sum of g (w, d_k), at f32 accuracy."""
        return dot(jnp.where(self.seen, 1.0, 0.0), g, NN, EXACT)

    def reverse_running(self, dgam):
        """dg_m = Σ_{i ≥ m} dΓ_i inside a chunk."""
        return dot(jnp.where(self.seen, 1.0, 0.0), dgam, TN, EXACT)

    def row_of_each(self, ref, d):
        """Row ``d`` of every sub-block of ``ref``, each over its sub-block's
        rows: (w, d_k)."""
        width = ref.shape[-1]
        return jnp.concatenate([
            jnp.broadcast_to(ref[i * SUB + d:i * SUB + d + 1, :], (SUB, width))
            for i in range(self.m)], axis=0)

    def about_rows(self, gam):
        """What the sub-blocks BELOW the diagonal carry, about the reference
        row r = the last before a sub-block's first (0 at a chunk's start):
        ``fall`` = e^{Γ_i − Γ_r} for the rows i of every sub-block, and for each
        sub-block with earlier ones in its chunk ``(its index, rise)``, rise =
        e^{Γ_r − Γ_j} for the columns j of its chunk before it, 0 elsewhere
        (the mask goes on the exponent too).  (w, d_k) f32 each."""
        width = gam.shape[-1]
        reference = {i: self.gam_ref[i * SUB - 1:i * SUB, :] for i, _ in self.lower}
        fall = jnp.exp(gam - jnp.concatenate([
            jnp.broadcast_to(reference[i], (SUB, width)) if i in reference
            else jnp.zeros((SUB, width), F32) for i in range(self.m)], axis=0))
        rises = []
        for i, start in self.lower:
            earlier = (self.token >= start) & (self.token < i * SUB)
            rises.append((i, jnp.where(
                earlier, jnp.exp(jnp.where(earlier, reference[i] - gam, 0.0)), 0.0)))
        return fall, rises

    def in_block(self, i):
        return (self.token // SUB) == i

    def pair(self, gam, d):
        """Column ``d`` of every diagonal sub-block: ``k_j ⊙ e^{Γ_i − Γ_j}`` (w,
        d_k) for j the sub-block's row d and i ≥ j (above it the exponent is
        held at 0 and the caller masks), the exponential alone, and where the
        column lies."""
        e = jnp.exp(jnp.minimum(gam - self.row_of_each(self.gam_ref, d), 0.0))
        return self.row_of_each(self.k_ref, d) * e, e, self.cols == self.first + d


def _grams(stack: _Stack, xs, k, gam, about, cdt):
    """``Σ_c x_ic k_jc e^{Γ_ic − Γ_jc}`` (w, w) f32 for each ``(x, strict)`` of
    ``xs`` (x, k, gam (w, d_k) f32; ``about`` = ``stack.about_rows(gam)``):
    over i > j (``strict``) or i ≥ j of a chunk, 0 elsewhere.  Below the
    diagonal sub-blocks matrix products with operands in ``cdt``, on them f32
    sums pair by pair."""
    fall, rises = about
    lhs = [(x * fall).astype(cdt) for x, _ in xs]
    grams = [jnp.zeros((stack.w, stack.w), F32) for _ in xs]
    for i, rise in rises:
        cols = (k * rise).astype(cdt)
        grams = [g + jnp.where(stack.in_block(i), dot(x, cols, NT), 0.0)
                 for g, x in zip(grams, lhs)]

    # written out: a column's chain (two row reads, an exponential, a sum across
    # lanes) is long and narrow, and only a written-out loop overlaps them
    for d in range(SUB):
        weighted, _, here = stack.pair(gam, d)
        grams = [jnp.where(here & (stack.strict if strict else stack.seen),
                           jnp.sum(x * weighted, axis=1, keepdims=True), g)
                 for g, (x, strict) in zip(grams, xs)]
    return grams


def _gram_cotangents(stack: _Stack, da, db, q, k, gam, about, cab_ref, cdt):
    """From dA (strictly lower) and dB (lower) of a chunk: R(dA, K), R(dB, K)
    and C(dA, K) + C(dB, Q) of the module's docstring, (w, d_k) f32 each.
    ``cab_ref``: (w, d_k) f32 scratch for the diagonal sub-blocks' columns."""
    fall, rises = about
    k_rows, q_rows = (k * fall).astype(cdt), (q * fall).astype(cdt)
    ra = rb = cab = jnp.zeros(k.shape, F32)
    for i, rise in rises:
        cols = (k * rise).astype(cdt)
        ma, mb = (jnp.where(stack.in_block(i), x, 0.0).astype(cdt) for x in (da, db))
        ra, rb = ra + dot(ma, cols, NN), rb + dot(mb, cols, NN)
        cab = cab + rise * (dot(ma, k_rows, TN) + dot(mb, q_rows, TN))
    ra, rb = ra * fall, rb * fall

    for d in range(SUB):
        weighted, e, here = stack.pair(gam, d)
        da_col, db_col = (jnp.sum(jnp.where(here, x, 0.0), axis=1, keepdims=True)
                          for x in (da, db))
        z = (da_col * k + db_col * q) * e
        for i in range(stack.m):  # row d of sub-block i takes its rows' sum
            cab_ref[i * SUB + d:i * SUB + d + 1, :] = jnp.sum(
                z[i * SUB:(i + 1) * SUB], axis=0, keepdims=True)
        ra, rb = ra + da_col * weighted, rb + db_col * weighted
    return ra, rb, cab + cab_ref[...]


# ---------------------------------------------------------------------------
# inside a chunk: T = (I + β A)⁻¹
# ---------------------------------------------------------------------------


def _inverse_kernel(chunk, w, groups, cdt):
    """``w`` rows hold ``w // chunk`` chunks: one block-diagonal w × w matrix."""
    from jax.experimental import pallas as pl

    per = w // chunk

    def kernel(k_ref, g_ref, b_ref, t_ref, gam_rows, k_rows):
        stack = _Stack(w, chunk, gam_rows, k_rows)
        level = round_levels(stack.rows, stack.cols, stack.strict, chunk)

        def group(i, carry):
            at = pl.ds(pl.multiple_of(i * w, w), w)
            k = k_ref[0, at, :].astype(F32)
            gam = stack.running(g_ref[0, at, :])
            gam_rows[...], k_rows[...] = gam, k
            (a,) = _grams(stack, [(k, True)], k, gam, stack.about_rows(gam), cdt)
            inv = inverse_rounds(column(b_ref[0, i], stack.eye) * a, level, stack.eye, chunk)
            for j in range(per):
                t_ref[0, i * per + j] = inv[j * chunk:(j + 1) * chunk, j * chunk:(j + 1) * chunk]
            return carry

        lax.fori_loop(0, groups, group, 0)

    return kernel


@functools.partial(jax.jit, static_argnames=("h", "chunk", "nb", "interpret"))
def _chunk_inverse(k, g, beta, h, chunk, nb, interpret):
    """k (B, S, H·d_k), g the same f32, beta (BH, S) f32 → T (BH, N, C, C) f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, width = k.shape
    bh, dk, n = b * h, width // h, s // chunk
    w = max(chunk, STACK)
    groups = nb * chunk // w
    tokens = pl.BlockSpec((1, nb * chunk, dk), lambda i, j: (i // h, j, i % h))
    return pl.pallas_call(
        _inverse_kernel(chunk, w, groups, k.dtype),
        out_shape=jax.ShapeDtypeStruct((bh, n, chunk, chunk), F32, vma=vma_union(k, g, beta)),
        grid=(bh, n // nb),
        in_specs=[tokens, tokens, pl.BlockSpec((1, groups, 1, w), lambda i, j: (i, j, 0, 0))],
        out_specs=pl.BlockSpec((1, nb, chunk, chunk), lambda i, j: (i, j, 0, 0)),
        scratch_shapes=[pltpu.VMEM((w, dk), F32), pltpu.VMEM((w, dk), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=INVERSE_KERNEL,
    )(k, g, beta.reshape(bh, s // w, 1, w))


# ---------------------------------------------------------------------------
# along the sequence
# ---------------------------------------------------------------------------


def _chunk_forward(stack: _Stack, q, k, v, g, t, b_row, state, with_a, cdt):
    """One chunk from its entering state (f32 | the compute dtype): what the
    forward and the backward pass share — B always, A where ``with_a``."""
    chunk, dv = stack.chunk, v.shape[-1]
    q, k = q.astype(F32), k.astype(F32)
    gam = stack.running(g)
    stack.gam_ref[...], stack.k_ref[...] = gam, k
    e_gamma = jnp.exp(gam)
    to_end = jnp.exp(stack.gam_ref[chunk - 1:chunk, :] - gam)
    # e^{Γ_C} down the state's rows: g's column sums over the lanes of a state
    last = jnp.exp(dot(g, jnp.ones((chunk, dv), F32), TN, EXACT))
    held = state.astype(cdt)
    kg, qg, k_end = ((x * e).astype(cdt) for x, e in ((k, e_gamma), (q, e_gamma), (k, to_end)))
    rhs = (v.astype(F32) - dot(kg, held, NN)).astype(cdt)
    t_beta = (t * b_row).astype(cdt)  # the row scales go on T's columns
    u = dot(t_beta, rhs, NN).astype(cdt)
    about = stack.about_rows(gam)
    *a, b = _grams(stack, [(k, True)] * with_a + [(q, False)], k, gam, about, cdt)
    return dict(a=a[0] if a else None, b=b, about=about, q=q, k=k, gam=gam, e_gamma=e_gamma,
                to_end=to_end, last=last, held=held, kg=kg, qg=qg, k_end=k_end, rhs=rhs,
                t_beta=t_beta, u=u)


def _fwd_kernel(chunk, nb, cdt, save):
    from jax.experimental import pallas as pl

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, o_ref, *rest):
        entering_ref, (state, gam_rows, k_rows) = (rest[0], rest[1:]) if save else (None, rest)
        stack = _Stack(chunk, chunk, gam_rows, k_rows)

        @pl.when(pl.program_id(1) == 0)
        def _start():
            state[...] = jnp.zeros_like(state)

        def one(c, carry):
            at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            f = _chunk_forward(stack, q_ref[0, at, :], k_ref[0, at, :], v_ref[0, at, :],
                               g_ref[0, at, :], t_ref[0, c], b_ref[0, c], state[...], False, cdt)
            if save:
                entering_ref[0, c] = f["held"]
            o_ref[0, at, :] = dot(f["qg"], f["held"], NN) + dot(f["b"].astype(cdt), f["u"], NN)
            state[...] = f["last"] * state[...] + dot(f["k_end"], f["u"], TN)
            return carry

        lax.fori_loop(0, nb, one, 0)

    return kernel


def _specs(h, nb, chunk, dk, dv, index):
    """Block specs of a (batch · heads, blocks of chunks) grid: q | k | g and
    v | o | do token-major (cell i is head i % h of batch i // h), β, T and
    the entering states by head."""
    from jax.experimental import pallas as pl

    return dict(
        qk=pl.BlockSpec((1, nb * chunk, dk), lambda i, j: (i // h, index(j), i % h)),
        v=pl.BlockSpec((1, nb * chunk, dv), lambda i, j: (i // h, index(j), i % h)),
        scalar=pl.BlockSpec((1, nb, 1, chunk), lambda i, j: (i, index(j), 0, 0)),
        t=pl.BlockSpec((1, nb, chunk, chunk), lambda i, j: (i, index(j), 0, 0)),
        state=pl.BlockSpec((1, nb, dk, dv), lambda i, j: (i, index(j), 0, 0)),
    )


def _scratch(chunk, dk, dv, more=0):
    """The state | its cotangent, then Γ and K (f32) of the chunk in hand, then
    ``more`` arrays of their size."""
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM((dk, dv), F32)] + [pltpu.VMEM((chunk, dk), F32)] * (2 + more)


@functools.partial(jax.jit, static_argnames=("h", "chunk", "nb", "save", "interpret"))
def _scan_forward(q, k, v, g, beta, t, h, chunk, nb, save, interpret):
    """→ o (B, S, H·d_v) f32 and, if ``save``, every chunk's entering state
    (BH, N, d_k, d_v) in the compute dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = q.shape
    bh, dk, dv, n, cdt = b * h, q.shape[-1] // h, v.shape[-1] // h, s // chunk, q.dtype
    vma = vma_union(q, k, v, g, beta, t)
    spec = _specs(h, nb, chunk, dk, dv, lambda j: j)
    out_shape = [jax.ShapeDtypeStruct(v.shape, F32, vma=vma)]
    out_specs = [spec["v"]]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((bh, n, dk, dv), cdt, vma=vma))
        out_specs.append(spec["state"])
    out = pl.pallas_call(
        _fwd_kernel(chunk, nb, cdt, save),
        out_shape=out_shape,
        grid=(bh, n // nb),
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["qk"], spec["scalar"], spec["t"]],
        out_specs=out_specs,
        scratch_shapes=_scratch(chunk, dk, dv),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=FWD_KERNEL,
    )(q, k, v, g, beta.reshape(bh, n, 1, chunk), t)
    return tuple(out) if save else (out[0], None)


def _bwd_kernel(chunk, nb, cdt):
    from jax.experimental import pallas as pl

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, entering_ref, do_ref,
               dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate, gam_rows, k_rows, cab_rows):
        stack = _Stack(chunk, chunk, gam_rows, k_rows)
        seen, strict, eye = stack.seen, stack.strict, stack.eye
        is_last = stack.token == chunk - 1

        @pl.when(pl.program_id(1) == 0)
        def _start():
            dstate[...] = jnp.zeros_like(dstate)

        def one(step, carry):
            c = nb - 1 - step
            at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            t, b_row = t_ref[0, c], b_ref[0, c]
            f = _chunk_forward(stack, q_ref[0, at, :], k_ref[0, at, :], v_ref[0, at, :],
                               g_ref[0, at, :], t, b_row, entering_ref[0, c], True, cdt)
            q, k, gam, e_gamma, to_end, held = (
                f[x] for x in ("q", "k", "gam", "e_gamma", "to_end", "held"))
            do = do_ref[0, at, :].astype(cdt)
            leaving = dstate[...]  # the cotangent of the state this chunk leaves
            leaving_cdt = leaving.astype(cdt)

            du = (dot(f["b"].astype(cdt), do, TN) + dot(f["k_end"], leaving_cdt, NN)).astype(cdt)
            db = jnp.where(seen, dot(do, f["u"], NT), 0.0)
            drhs = dot(f["t_beta"], du, TN)
            dt_beta = dot(du, f["rhs"], NT)
            drhs_cdt = drhs.astype(cdt)
            dv_ref[0, at, :] = drhs.astype(dv_ref.dtype)
            dstate[...] = f["last"] * leaving + dot(f["qg"], do, TN) - dot(f["kg"], drhs_cdt, TN)

            # T = (I + β A)⁻¹: d(β A) = −Tᵀ dT Tᵀ, strictly lower
            dba = jnp.where(strict, -dot(dot(t, dt_beta * b_row, TN, EXACT), t, NT, EXACT), 0.0)
            db_ref[0, c] = (jnp.sum(dt_beta * t, axis=0, keepdims=True)
                            + row(jnp.sum(dba * f["a"], axis=1, keepdims=True), eye))
            ra, rb, cab = _gram_cotangents(stack, column(b_row, eye) * dba, db, q, k, gam,
                                           f["about"], cab_rows, cdt)

            # through the operands e^Γ ⊙ Q, e^Γ ⊙ K and e^{Γ_C − Γ} ⊙ K
            dqg = dot(do, held, NT) * e_gamma
            dkg = -dot(drhs_cdt, held, NT) * e_gamma
            dk_end = dot(f["u"], leaving_cdt, NT) * to_end
            dq_ref[0, at, :] = (rb + dqg).astype(dq_ref.dtype)
            dk_ref[0, at, :] = (ra + cab + dkg + dk_end).astype(dk_ref.dtype)
            # Γ's cotangent a row; the chunk's last row also takes e^{Γ_C − Γ}'s
            # and e^{Γ_C}'s (the state's rows: summed over its lanes into a row)
            d_end = k * dk_end
            d_last = dot(jnp.ones((8, held.shape[-1]), F32),
                         held.astype(F32) * leaving * f["last"], NT, EXACT)[:1]
            dgam = k * (ra - cab + dkg - dk_end) + q * (rb + dqg)
            dgam = dgam + jnp.where(is_last, jnp.sum(d_end, axis=0, keepdims=True) + d_last, 0.0)
            dg_ref[0, at, :] = stack.reverse_running(dgam)
            return carry

        lax.fori_loop(0, nb, one, 0)

    return kernel


@functools.partial(jax.jit, static_argnames=("h", "chunk", "nb", "interpret"))
def _scan_backward(q, k, v, g, beta, t, entering, do, h, chunk, nb, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = q.shape
    bh, dk, dv, n, cdt = b * h, q.shape[-1] // h, v.shape[-1] // h, s // chunk, q.dtype
    vma = vma_union(q, k, v, g, beta, t, entering, do)
    last = n // nb - 1
    # from the last block to the first
    spec = _specs(h, nb, chunk, dk, dv, lambda j: last - j)
    by_chunk = beta.reshape(bh, n, 1, chunk)
    shape = lambda x, dtype=None: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, dtype or x.dtype, vma=vma)
    dq, dk_, dv_, dg, db = pl.pallas_call(
        _bwd_kernel(chunk, nb, cdt),
        out_shape=[shape(q), shape(k), shape(v), shape(g, F32), shape(by_chunk, F32)],
        grid=(bh, n // nb),
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["qk"], spec["scalar"], spec["t"],
                  spec["state"], spec["v"]],
        out_specs=[spec["qk"], spec["qk"], spec["v"], spec["qk"], spec["scalar"]],
        scratch_shapes=_scratch(chunk, dk, dv, more=1),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=BWD_KERNEL,
    )(q, k, v, g, by_chunk, t, entering, do)
    return dq, dk_, dv_, dg.astype(g.dtype), db.reshape(beta.shape).astype(beta.dtype)


# ---------------------------------------------------------------------------
# the rule with its backward pass
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _rule(q, k, v, g, beta, h, chunk, blocks, interpret):
    t = _chunk_inverse(k, g, beta, h, chunk, blocks[0], interpret)
    return _scan_forward(q, k, v, g, beta, t, h, chunk, blocks[1], False, interpret)[0]


def _rule_fwd(q, k, v, g, beta, h, chunk, blocks, interpret):
    # T takes its name before the walk reads it (gated_delta_kernels._rule_fwd)
    t = checkpoint_name(_chunk_inverse(k, g, beta, h, chunk, blocks[0], interpret), SAVED[0])
    o, entering = _scan_forward(q, k, v, g, beta, t, h, chunk, blocks[1], True, interpret)
    entering, o = checkpoint_name(entering, SAVED[1]), checkpoint_name(o, SAVED[2])
    return o, (q, k, v, g, beta, t, entering)


def _rule_bwd(h, chunk, blocks, interpret, res, do):
    return _scan_backward(*res, do, h, chunk, blocks[2], interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def kda_kernels(q, k, v, g, beta, chunk, blocks, interpret=False):
    """q, k (B, S, H, d_k) and v (B, S, H, d_v) in the compute dtype, g
    (B, S, H, d_k) and beta (B, S, H) f32; ``blocks`` = chunks a grid step of
    the three kernels (each divides S / chunk; the first is a whole number of
    stacks).  Returns o (B, S, H, d_v) f32.  Differentiable in all five."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    o = _rule(q.reshape(b, s, h * dk), k.reshape(b, s, h * dk), v.reshape(b, s, h * dv),
              g.reshape(b, s, h * dk), by_head(beta), h, chunk, tuple(blocks), interpret)
    return o.reshape(b, s, h, dv)
