"""The selective scan of Mamba-1 (S6) — a linear recurrence with an
input-dependent step size a CHANNEL and a decay a (channel, state entry).

A channel c keeps N state entries along the sequence, zero at its start.  At
token t, with ``Δ_t[c] > 0`` the channel's step size, ``A[c, n] < 0`` the
entry's rate, ``x_t[c]`` the channel's input and ``B_t``, ``C_t`` (N,) the
token's:

    h_t[c, n] = exp(Δ_t[c] A[c, n]) h_{t−1}[c, n] + Δ_t[c] B_t[n] x_t[c]
    y_t[c]    = Σ_n C_t[n] h_t[c, n]  +  D[c] x_t[c]

(:func:`selective_scan_recurrence`: one ``lax.scan`` over the tokens, the
definition and the oracle; autodiff through it keeps a state a token.)

Where ``ops/ssd.py``'s scan (Mamba-2) has ONE decay a head — so that a chunk
is matrix products with a decay matrix between them — this one has a decay an
entry of the state, and no matrix form exists: every entry is its own scalar
recurrence, 5 operations a token forward, element-wise, for the vector unit
and the memory, not the MXU.

:func:`selective_scan` computes it with a backward pass that keeps a state a
CHUNK of tokens (``chunk``: :data:`CHUNK`), behind a ``custom_vjp``: the
forward pass carries the state (B, N, C) f32 — the channels along the lanes —
token by token and writes the state entering every chunk; the backward pass
walks the chunks from the last, rebuilds one chunk's states from the one that
entered it, and runs the adjoint recurrence ``g_t = C_t ⊗ dy_t + exp(Δ_{t+1}
A) g_{t+1}`` backwards through them, reading every gradient off g and h a
token at a time (dA summed in an f32 carry).  At 16 384 tokens, 5120 channels
and 16 entries a state is 320 KiB: a token's worth kept would be 5 GiB, a
chunk's worth is 128 of them once and 128 entering states beside.  Δ, the
decay, the state and every sum are f32 whatever the operands' dtype; every
exponent is of a non-positive number, so nothing overflows however strong the
decay (a channel whose decay underflows just forgets).  A sequence that
``chunk`` does not divide is padded at its end with tokens of step size 0,
which leave the state as it is, and the padding is cut off again.

**Token-major**: x, Δ ``(B, S, C)``, B and C ``(B, S, N)``, A ``(C, N)``, D
``(C,)``; y ``(B, S, C)`` in x's dtype — as the projections around the scan
write and read them.

The forward rule gives the chunks' entering states and y the names in
:data:`SAVED`, so a caller that rebuilds its layer in the backward pass
(``models/moe_family.walk``) can keep them by name
(``save_only_these_names(*SAVED)``) and the rebuilt layer does not scan a
second time.

One implementation, XLA's (``lax.scan`` over chunks around ``lax.scan`` over a
chunk's tokens).  A traced call bumps ``selective_scan_xla_traces``
(``bps.get_robustness_counters()``), as ``ssd_xla_traces`` counts Mamba-2's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from byteps_tpu.core.telemetry import counters
from byteps_tpu.ops._dispatch import vma_union

#: tokens a chunk: the backward pass holds one chunk's states, (chunk, B, N,
#: C) f32, and the forward pass keeps a state a chunk
CHUNK = 128
#: tokens a step of the scans over a chunk's tokens is unrolled by
UNROLL = 8

#: what the forward leaves for the backward, by name: a ``jax.checkpoint``
#: whose policy saves these does not run the forward scan again in its
#: backward pass, as ``ssd_kernels.SAVED``
SAVED = ("selective_scan_entering", "selective_scan_out")

F32 = jnp.float32


def selective_scan_recurrence(x, dt, a, b, c, d):
    """The scan token by token.  x, dt (B, S, C), a (C, N), b and c (B, S,
    N), d (C,); the state is carried in dt's dtype.  Returns y (B, S, C) in
    dt's dtype."""
    st = dt.dtype
    a, d = a.astype(st), d.astype(st)

    def token(h, xs):
        x_t, dt_t, b_t, c_t = xs  # (B, C) x 2, (B, N) x 2
        h = (jnp.exp(dt_t[..., None] * a) * h
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return h, jnp.einsum("bcn,bn->bc", h, c_t) + d * x_t

    xs = tuple(jnp.moveaxis(t.astype(st), 1, 0) for t in (x, dt, b, c))
    _, y = lax.scan(token, jnp.zeros((x.shape[0], *a.shape), st), xs)
    return jnp.moveaxis(y, 0, 1)


def selective_scan(x, dt, a, b, c, d, chunk: int = CHUNK):
    """x (B, S, C), dt (B, S, C) > 0 — the softplus taken by the caller —, a
    (C, N) < 0, b and c (B, S, N), d (C,).  Returns y (B, S, C) in x's dtype,
    ``D x`` included.  Differentiable in all six; the backward pass keeps a
    state a ``chunk`` tokens."""
    counters().bump("selective_scan_xla_traces")  # once a traced call
    s = x.shape[1]
    chunk = min(chunk, s)
    pad = -s % chunk
    if pad:  # a token of step size 0 leaves the state as it is
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (x, dt, b, c))
    # under shard_map the rates and D are replicated and the tokens vary: typed
    # as the tokens vary, their cotangents are summed over the ranks by this
    # cast's transpose
    vma = vma_union(x, dt, b, c)
    x, dt, a, b, c, d = (_varying(t, vma) for t in (x, dt.astype(F32), a.astype(F32), b, c,
                                                    d.astype(F32)))
    y = _scan(x, dt, a, b, c, d, chunk)
    return y[:, :s] if pad else y


def _varying(t, vma):
    need = tuple(vma - jax.typeof(t).vma)
    return lax.pcast(t, need, to="varying") if need else t


def _chunks(t, chunk: int):
    """(B, S, ·) → (chunks, chunk, B, ·): tokens first, a chunk at a time."""
    b, s, w = t.shape
    return jnp.moveaxis(t, 1, 0).reshape(s // chunk, chunk, b, w)


def _unchunk(t):
    """(chunks, chunk, B, ·) → (B, S, ·)."""
    n, chunk, b, w = t.shape
    return jnp.moveaxis(t.reshape(n * chunk, b, w), 0, 1)


def _decay(dt_t, at):
    """exp(Δ_t ⊗ A): (B, C), (N, C) → (B, N, C), every exponent <= 0."""
    return jnp.exp(dt_t[:, None, :] * at)


def _step(h, x_t, dt_t, b_t, at):
    """One token's state from the last: h (B, N, C) f32, the channels along
    the lanes; x_t, dt_t (B, C) f32, b_t (B, N) f32, at = Aᵀ (N, C)."""
    return _decay(dt_t, at) * h + (dt_t * x_t)[:, None, :] * b_t[:, :, None]


def _zero_state(x, at):
    """(B, N, C) f32 zeros, typed as x varies (under ``shard_map``)."""
    return _varying(jnp.zeros((x.shape[0], *at.shape), F32), jax.typeof(x).vma)


def _forward(x, dt, a, b, c, d, chunk):
    """→ (y (B, S, C) in x's dtype, the states entering the chunks (chunks, B,
    N, C) f32)."""
    at = a.T  # (N, C): channels along the lanes

    def token(h, xs):
        x_t, dt_t, b_t, c_t = xs
        x_t, b_t, c_t = x_t.astype(F32), b_t.astype(F32), c_t.astype(F32)
        h = _step(h, x_t, dt_t, b_t, at)
        return h, (jnp.sum(c_t[:, :, None] * h, axis=1) + d * x_t).astype(x.dtype)

    def a_chunk(h, xs):
        left, y = lax.scan(token, h, xs, unroll=UNROLL)
        return left, (h, y)

    xs = tuple(_chunks(t, chunk) for t in (x, dt, b, c))
    _, (entering, y) = lax.scan(a_chunk, _zero_state(x, at), xs)
    return _unchunk(y), entering


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, b, c, d, chunk):
    return _forward(x, dt, a, b, c, d, chunk)[0]


def _scan_fwd(x, dt, a, b, c, d, chunk):
    y, entering = _forward(x, dt, a, b, c, d, chunk)
    entering, y = checkpoint_name(entering, SAVED[0]), checkpoint_name(y, SAVED[1])
    return y, (x, dt, a, b, c, d, entering)


def _scan_bwd(chunk, kept, dy):
    """The chunks from the last to the first.  A chunk: its states rebuilt
    from the one that entered it, then the adjoint recurrence backwards
    through its tokens.  With g_t the state's cotangent after token t's read
    (``C_t ⊗ dy_t`` plus what the later tokens hand back through their
    decays) and ``p_t = g_t ⊙ exp(Δ_t A) ⊙ h_{t−1}``:

        dΔ_t = Σ_n p_t A + x_t Σ_n g_t B_t      dx_t = Δ_t Σ_n g_t B_t + D dy_t
        dB_t = Σ_c g_t Δ_t x_t                  dC_t = Σ_c h_t dy_t
        dA   = Σ_t p_t Δ_t                      dD   = Σ_t dy_t x_t
    """
    x, dt, a, b, c, d, entering = kept
    at = a.T

    def rebuild(h, xs):
        x_t, dt_t, b_t = xs
        return _step(h, x_t.astype(F32), dt_t, b_t.astype(F32), at), h  # emits h_{t−1}

    def token(carry, xs):
        back, da = carry  # exp(Δ_{t+1} A) g_{t+1}; dA so far, (B, N, C)
        x_t, dt_t, b_t, c_t, dy_t, before = xs
        x_t, b_t, c_t, dy_t = (t.astype(F32) for t in (x_t, b_t, c_t, dy_t))
        decay = _decay(dt_t, at)
        h_t = decay * before + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        g = back + c_t[:, :, None] * dy_t[:, None, :]
        p = g * decay * before
        gb = jnp.sum(g * b_t[:, :, None], axis=1)  # (B, C)
        grads = (dt_t * gb + d * dy_t,  # dx
                 jnp.sum(p * at, axis=1) + x_t * gb,  # dΔ
                 jnp.sum(g * (dt_t * x_t)[:, None, :], axis=2),  # dB
                 jnp.sum(h_t * dy_t[:, None, :], axis=2))  # dC
        return (decay * g, da + p * dt_t[:, None, :]), grads

    def a_chunk(carry, xs):
        x_, dt_, b_, c_, dy_, h0 = xs
        _, before = lax.scan(rebuild, h0, (x_, dt_, b_), unroll=UNROLL)
        return lax.scan(token, carry, (x_, dt_, b_, c_, dy_, before), reverse=True,
                        unroll=UNROLL)

    zero = _zero_state(x, at)
    xs = tuple(_chunks(t, chunk) for t in (x, dt, b, c, dy)) + (entering,)
    (_, da), (dx, ddt, db, dc) = lax.scan(a_chunk, (zero, zero), xs, reverse=True)
    dd = jnp.sum(dy.astype(F32) * x.astype(F32), axis=(0, 1))
    return (_unchunk(dx).astype(x.dtype), _unchunk(ddt), jnp.sum(da, axis=0).T,
            _unchunk(db).astype(b.dtype), _unchunk(dc).astype(c.dtype), dd)


_scan.defvjp(_scan_fwd, _scan_bwd)
