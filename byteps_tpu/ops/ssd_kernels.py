"""The chunked selective state-space scan as Pallas TPU kernels: a chunk
lives in VMEM from its first product to its last.

Two kernels, the mathematics of ``ops/ssd.py``'s docstring:

``ssd_scan_fwd`` — along the sequence.  Grid (batch × groups [parallel],
blocks of chunks [sequential]); the N × P f32 states of a group's heads stay
in VMEM scratch across the chunk axis, side by side along the lanes
(N, r·P).  A chunk, with ``X̃ = dt ∘ x``: ``G = C Bᵀ`` once a group, a
head's ``y = (G ⊙ Λ) X̃ + e^γ ∘ (C h₀)``,
``h ← e^{γ_C} h₀ + Bᵀ (e^{γ_C − γ} ∘ X̃)``.
Writes y (f32) and, where the scan is differentiated, the chunk's entering
states in f32.

``ssd_scan_bwd`` — the same walk from the last chunk to the first carrying
dh, a chunk's forward rebuilt from its entering state; gradients for x, B
and C (summed over a group's heads), dt (through ``X̃``) and the log-decay
``dt · a`` (from which XLA takes a's, and dt's other half).

The ``custom_vjp``'s forward rule gives the entering states and y the names in
``SAVED``, so a caller that rebuilds its layer in the backward pass
(``jax.checkpoint``) can keep them by a policy and run the forward kernel
once a step — ``models/ssm_moe._hidden`` does, as the attention part keeps the
flash kernel's output and row statistics (its layers are a Python loop,
``moe_family.walk``, so a kept array crosses no ``lax.scan``'s stack:
``ops/gated_delta_kernels.py``'s docstring has what that would cost).

The operands are token-major, as the projection before the scan writes them:
x, y and their cotangents ``(B, S, H·P)``, B and C ``(B, S, G·N)``, heads and
groups side by side along the lanes.  Grid cell ``(i, j)`` finds group
``i % G`` of batch ``i // G`` by its index map — its r heads as one block
``r·P`` lanes wide, its B and C as one block of N — so no head-major copy of
any of them exists.  The per-token scalars (dt and ``dt · a``) and the
entering states are the kernels' own: heads down the rows, ``(B·H, …)``, and
``(B·G, chunks, N, r·P)``.

A head narrower than the 128 lanes shares its lane tile with its neighbours
(two heads of 64): what a head alone scales or decays is spread over the
tile's lanes by a select, and a product that must keep the heads apart takes
one operand masked to the head's lanes — so every load, store and product is
on whole tiles.

dt, γ, Λ and the states are f32; every product takes its operands in the
compute dtype and accumulates in f32, with the casts XLA's form makes
(``G ⊙ Λ``, ``X̃``, ``e^{γ_C − γ} ∘ X̃`` and the entering state read by C are
operands).  Per-token scalars arrive as rows (chunk positions along the
lanes) and are turned into columns by a masked sum: no transposes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from byteps_tpu.ops._chunk import F32, NN, NT, TN, by_head, column, decays, dot, iotas, row, total
from byteps_tpu.ops._dispatch import LANES, vma_union

#: the kernels' names: a trace files their time under these
FWD_KERNEL, BWD_KERNEL = "ssd_scan_fwd", "ssd_scan_bwd"

#: the chunks' entering states and y carry these names wherever the scan is
#: differentiated: a ``jax.checkpoint`` whose policy saves them
#: (``save_only_these_names(*SAVED)``) does not run ``ssd_scan_fwd`` again in
#: its backward pass, as ``gated_delta_kernels.SAVED``
SAVED = ("ssd_entering", "ssd_out")


def heads_a_tile(p: int) -> int:
    """Heads of ``p`` channels that share one tile of lanes (1 where a head
    fills whole tiles)."""
    return max(LANES // p, 1)


def _spread(per_head, head_of):
    """The heads' own (C, 1) | (1, 1) values, each over its head's lanes of
    the tile: (C, w) | (1, w) — or the one head's value as it is, which
    broadcasts."""
    out = per_head[0]
    for i, value in enumerate(per_head[1:], 1):
        out = jnp.where(head_of == i, value, out)
    return out


def _only(i, x, head_of, k):
    """x (C, w) where it is head i's lanes, 0 elsewhere."""
    return x if k == 1 else jnp.where(head_of == i, x, 0.0)


def _tile_parts(x, c, cb, held, dt_rows, g_rows, masks, head_of):
    """One chunk of one lane tile's heads from their entering states ``held``
    (N, w) in the compute dtype: what the forward and the backward kernel
    share.  x (C, w), the group's c (C, N) and ``cb = C Bᵀ``, the heads' dt
    and log-decay as rows.  Per head (lists): the decay matrix, ``G ⊙ Λ``,
    e^γ, e^{γ_C − γ}, e^{γ_C}; over the tile's lanes: dt, e^γ, e^{γ_C − γ},
    e^{γ_C}, x and X̃ in f32, and ``C h₀``."""
    seen, eye = masks
    gam_cols, decay = zip(*(decays(g, seen, eye) for g in g_rows))
    gam_ends = [jnp.sum(g, axis=1, keepdims=True) for g in g_rows]  # (1, 1)
    e_gammas = [jnp.exp(gam) for gam in gam_cols]
    to_ends = [jnp.exp(end - gam) for end, gam in zip(gam_ends, gam_cols)]
    lasts = [jnp.exp(end) for end in gam_ends]
    dt = _spread([column(d, eye) for d in dt_rows], head_of)
    x = x.astype(F32)
    return dict(decay=decay, mixed=[cb * d for d in decay], e_gammas=e_gammas, to_ends=to_ends,
                lasts=lasts, dt=dt, x=x, xdt=x * dt, e_gamma=_spread(e_gammas, head_of),
                to_end=_spread(to_ends, head_of), last=_spread(lasts, head_of),
                read=dot(c, held, NN))


def _fwd_kernel(chunk, nb, r, p, cdt, save):
    from jax.experimental import pallas as pl

    k = heads_a_tile(p)
    w = k * p

    def kernel(x_ref, b_ref, c_ref, dt_ref, g_ref, y_ref, *rest):
        entering_ref, state = rest if save else (None, rest[0])
        rows, cols = iotas(chunk)
        masks = (rows >= cols, rows == cols)
        head_of = lax.broadcasted_iota(jnp.int32, (1, w), 1) // p

        @pl.when(pl.program_id(1) == 0)
        def _start():
            state[...] = jnp.zeros_like(state)

        def one(n, carry):
            at = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
            b, c = b_ref[0, at, :], c_ref[0, at, :]
            cb = dot(c, b, NT)
            for t in range(r // k):
                lanes = slice(t * w, (t + 1) * w)
                heads = range(t * k, (t + 1) * k)
                entering = state[:, lanes]
                if save:
                    entering_ref[0, n, :, lanes] = entering
                f = _tile_parts(x_ref[0, at, lanes], c, cb, entering.astype(cdt),
                                [dt_ref[h, n] for h in heads], [g_ref[h, n] for h in heads],
                                masks, head_of)
                y = f["e_gamma"] * f["read"]
                for i in range(k):
                    y = y + dot(f["mixed"][i].astype(cdt),
                                _only(i, f["xdt"], head_of, k).astype(cdt), NN)
                y_ref[0, at, lanes] = y
                state[:, lanes] = f["last"] * entering + dot(
                    b, (f["xdt"] * f["to_end"]).astype(cdt), TN)
            return carry

        lax.fori_loop(0, nb, one, 0)

    return kernel


def _specs(groups, r, nb, chunk, n_state, p, index):
    """Block specs of a (batch · groups, blocks of chunks) grid: x | y | dy
    and b | c token-major (cell i is group i % groups of batch i // groups,
    its r heads side by side), dt | log-decay by head, the entering states by
    group."""
    from jax.experimental import pallas as pl

    return dict(
        x=pl.BlockSpec((1, nb * chunk, r * p), lambda i, j: (i // groups, index(j), i % groups)),
        bc=pl.BlockSpec((1, nb * chunk, n_state),
                        lambda i, j: (i // groups, index(j), i % groups)),
        scalar=pl.BlockSpec((r, nb, 1, chunk), lambda i, j: (i, index(j), 0, 0)),
        state=pl.BlockSpec((1, nb, n_state, r * p), lambda i, j: (i, index(j), 0, 0)),
    )


def _vmem_bytes(nb, chunk, r, p, n_state, wide, narrow, scalars) -> int:
    """What a kernel asks of VMEM, from its shapes: every block-mapped operand
    twice (Pallas double-buffers them) — ``wide`` bytes an element of a
    (chunk, r·P) block summed over such operands, ``narrow`` of a (chunk, N)
    one, ``scalars`` rows of per-token scalars a head (a row takes a whole
    f32 tile), the entering states —, the carried state, a quarter over and
    4 MiB for a tile's matrices, and never under Mosaic's own default."""
    a_chunk = (chunk * (r * p * wide + n_state * narrow) + 4 * n_state * r * p
               + scalars * r * 8 * chunk * 4)
    return max((2 * nb * a_chunk + 4 * n_state * r * p) * 5 // 4 + 4 * 2**20, 16 * 2**20)


def _dims(x, b, dt, groups):
    """(B·G, r, P, N) of token-major x (B, S, H·P) and b (B, S, G·N) beside
    dt (B·H, S)."""
    bg = x.shape[0] * groups
    r = dt.shape[0] // bg
    return bg, r, x.shape[-1] // (groups * r), b.shape[-1] // groups


def _scan_forward(x, b, c, dt, g, groups, chunk, nb, save, interpret):
    """→ y (B, S, H·P) f32 and, if ``save``, every chunk's entering states
    (B·G, chunks, N, r·P) f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bg, r, p, n_state = _dims(x, b, dt, groups)
    n, cdt = x.shape[1] // chunk, x.dtype
    vma = vma_union(x, b, c, dt, g)
    spec = _specs(groups, r, nb, chunk, n_state, p, lambda j: j)
    by_chunk = lambda t: t.reshape(bg * r, n, 1, chunk)  # noqa: E731
    out_shape = [jax.ShapeDtypeStruct(x.shape, F32, vma=vma)]
    out_specs = [spec["x"]]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((bg, n, n_state, r * p), F32, vma=vma))
        out_specs.append(spec["state"])
    out = pl.pallas_call(
        _fwd_kernel(chunk, nb, r, p, cdt, save),
        out_shape=out_shape,
        grid=(bg, n // nb),
        in_specs=[spec["x"], spec["bc"], spec["bc"], spec["scalar"], spec["scalar"]],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((n_state, r * p), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(nb, chunk, r, p, n_state, cdt.itemsize + 4,
                                         2 * cdt.itemsize, 2)),
        interpret=interpret,
        name=FWD_KERNEL,
    )(x, b, c, by_chunk(dt), by_chunk(g))
    return tuple(out) if save else (out[0], None)


def _bwd_kernel(chunk, nb, r, p, cdt):
    from jax.experimental import pallas as pl

    k = heads_a_tile(p)
    w = k * p

    def kernel(x_ref, b_ref, c_ref, dt_ref, g_ref, entering_ref, dy_ref,
               dx_ref, db_ref, dc_ref, ddt_ref, dg_ref, dstate):
        rows, cols = iotas(chunk)
        seen, eye = rows >= cols, rows == cols
        is_last = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
        head_of = lax.broadcasted_iota(jnp.int32, (1, w), 1) // p

        @pl.when(pl.program_id(1) == 0)
        def _start():
            dstate[...] = jnp.zeros_like(dstate)

        def one(step, carry):
            n = nb - 1 - step
            at = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
            b, c = b_ref[0, at, :], c_ref[0, at, :]
            cb = dot(c, b, NT)
            dcb = jnp.zeros((chunk, chunk), F32)  # summed over the group's heads
            db = jnp.zeros(b.shape, F32)
            dc = jnp.zeros(c.shape, F32)
            for t in range(r // k):
                lanes = slice(t * w, (t + 1) * w)
                heads = range(t * k, (t + 1) * k)
                entering = entering_ref[0, n, :, lanes]
                held = entering.astype(cdt)
                f = _tile_parts(x_ref[0, at, lanes], c, cb, held,
                                [dt_ref[h, n] for h in heads], [g_ref[h, n] for h in heads],
                                (seen, eye), head_of)
                xdt = f["xdt"].astype(cdt)
                dy = dy_ref[0, at, lanes]
                leaving = dstate[:, lanes]  # the cotangent of the states this chunk leaves
                leaving_cdt = leaving.astype(cdt)
                b_dstate = dot(b, leaving_cdt, NN)  # cotangent of e^{γ_C − γ} ∘ X̃
                dxdt = f["to_end"] * b_dstate
                for i in range(k):
                    dy_i = _only(i, dy, head_of, k).astype(cdt)
                    dxdt = dxdt + dot(f["mixed"][i].astype(cdt), dy_i, TN)
                    dmixed = dot(dy_i, xdt, NT)
                    dcb = dcb + f["decay"][i] * dmixed
                    # the exponents γ_i − γ_j of Λ, e^γ, e^{γ_C − γ} and e^{γ_C}
                    dexp = f["mixed"][i] * dmixed
                    mine = functools.partial(_only, i, head_of=head_of, k=k)
                    d_e_gamma = jnp.sum(mine(dy * f["read"]), axis=1, keepdims=True)
                    d_to_end = (jnp.sum(mine(f["xdt"] * b_dstate), axis=1, keepdims=True)
                                * f["to_ends"][i])
                    d_last = total(mine(entering * leaving)) * f["lasts"][i]
                    dgam = (jnp.sum(dexp, axis=1, keepdims=True) + d_e_gamma * f["e_gammas"][i]
                            - d_to_end - column(jnp.sum(dexp, axis=0, keepdims=True), eye))
                    dgam = dgam + jnp.where(is_last, total(d_to_end) + d_last, 0.0)
                    # γ is the log-decay's running sum: dg_m = Σ_{i ≥ m} dγ_i, as a row
                    dg_ref[heads[i], n] = jnp.sum(jnp.where(seen, dgam, 0.0), axis=0,
                                                  keepdims=True)
                for i in range(k):  # dX̃ is whole now: X̃ = dt ∘ x
                    ddt_ref[heads[i], n] = row(jnp.sum(_only(i, dxdt * f["x"], head_of, k),
                                                       axis=1, keepdims=True), eye)
                dx_ref[0, at, lanes] = (dxdt * f["dt"]).astype(dx_ref.dtype)
                dread = (f["e_gamma"] * dy).astype(cdt)  # cotangent of C h₀
                dc = dc + dot(dread, held, NT)
                db = db + dot((f["xdt"] * f["to_end"]).astype(cdt), leaving_cdt, NT)
                dstate[:, lanes] = f["last"] * leaving + dot(c, dread, TN)
            dcb = dcb.astype(cdt)
            dc_ref[0, at, :] = (dc + dot(dcb, b, NN)).astype(dc_ref.dtype)
            db_ref[0, at, :] = (db + dot(dcb, c, TN)).astype(db_ref.dtype)
            return carry

        lax.fori_loop(0, nb, one, 0)

    return kernel


def _scan_backward(x, b, c, dt, g, entering, dy, groups, chunk, nb, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bg, r, p, n_state = _dims(x, b, dt, groups)
    n, cdt = x.shape[1] // chunk, x.dtype
    vma = vma_union(x, b, c, dt, g, entering, dy)
    last = n // nb - 1
    # from the last block to the first
    spec = _specs(groups, r, nb, chunk, n_state, p, lambda j: last - j)
    by_chunk = lambda t: t.reshape(bg * r, n, 1, chunk)  # noqa: E731
    shape = lambda t, dtype=None: jax.ShapeDtypeStruct(  # noqa: E731
        t.shape, dtype or t.dtype, vma=vma)
    dx, db, dc, ddt, dg = pl.pallas_call(
        _bwd_kernel(chunk, nb, r, p, cdt),
        out_shape=[shape(x), shape(b), shape(c), shape(by_chunk(dt), F32),
                   shape(by_chunk(g), F32)],
        grid=(bg, n // nb),
        in_specs=[spec["x"], spec["bc"], spec["bc"], spec["scalar"], spec["scalar"],
                  spec["state"], spec["x"]],
        out_specs=[spec["x"], spec["bc"], spec["bc"], spec["scalar"], spec["scalar"]],
        scratch_shapes=[pltpu.VMEM((n_state, r * p), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(nb, chunk, r, p, n_state, 2 * cdt.itemsize + 4,
                                         4 * cdt.itemsize, 4)),
        interpret=interpret,
        name=BWD_KERNEL,
    )(x, b, c, by_chunk(dt), by_chunk(g), entering, dy)
    return dx, db, dc, ddt.reshape(dt.shape).astype(dt.dtype), dg.reshape(g.shape).astype(g.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _rule(x, b, c, dt, g, groups, chunk, blocks, interpret):
    return _scan_forward(x, b, c, dt, g, groups, chunk, blocks[0], False, interpret)[0]


def _rule_fwd(x, b, c, dt, g, groups, chunk, blocks, interpret):
    y, entering = _scan_forward(x, b, c, dt, g, groups, chunk, blocks[0], True, interpret)
    entering, y = checkpoint_name(entering, SAVED[0]), checkpoint_name(y, SAVED[1])
    return y, (x, b, c, dt, g, entering)


def _rule_bwd(groups, chunk, blocks, interpret, res, dy):
    return _scan_backward(*res, dy, groups, chunk, blocks[1], interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def ssd_kernels(x, dt, a, b, c, groups, chunk, blocks, interpret=False):
    """x (B, S, H·P), b and c (B, S, G·N) in the compute dtype, dt (B, S, H)
    and a (H,) f32; ``blocks`` = chunks a grid step of the forward and the
    backward kernel (each divides S / chunk).  Returns y (B, S, H·P) f32.
    Differentiable in all five."""
    rows = by_head(dt)  # (B·H, S)
    decay = rows * jnp.tile(a, x.shape[0])[:, None]  # the log-decay dt · a
    return _rule(x, b, c, rows, decay, groups, chunk, tuple(blocks), interpret)
