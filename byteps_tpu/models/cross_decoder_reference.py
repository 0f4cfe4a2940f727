"""Plain reference of the decoder-hybrid-decoder family
(models/cross_decoder.py): the published equations of SambaY's block
(``modeling_phi4flash.py`` of Phi-4-mini-flash-reasoning) in straightforward
float32 ``jax.numpy`` — no kernel, no chunks, no remat, no blocks, nothing of
the program's.  Matrix products at ``highest`` precision.  The tests hold the
system to it; the benchmark keeps its own blocked copy
(benchmark/builders/phi4flash.py).

d = ``d_model``, d_i = ``expand`` · d, N = ``d_state``, R = ``dt_rank``.  ``LN``
is LayerNorm with scale and bias.  Every layer: ``x ← x + mixer(LN₁(x)); x ← x
+ MLP(LN₂(x))``, ``MLP(u) = W₂(silu(g) ⊙ v)`` with ``[g ‖ v] = W₁u``, no bias.
No positional encoding anywhere.  Tied head: ``logits = LN_f(x) Eᵀ`` over the
held rows.  By PUBLISHED layer index ℓ, n the published depth, half = n / 2 (ℓ
even → Mamba-1 for ℓ ≤ half, GMU above; ℓ odd → attention: over a window for ℓ
< half, full for ℓ = half + 1, cross above):

**Mamba-1**: ``[x̃ ‖ z] = W_in u`` (no bias); ``x̃ ← silu(conv(x̃) + b_conv)``
(depthwise, causal, ``conv_kernel`` taps); ``[δ ‖ B ‖ C] = W_x x̃`` (d_i → R + N
+ N, no bias); ``Δ = softplus(W_dt δ + b_dt)``; ``A = −exp(A_log)`` (d_i × N);
``h_t = exp(Δ_t ⊗ A) ⊙ h_{t−1} + (Δ_t ⊙ x̃_t) ⊗ B_t``, h₀ = 0 — token by token,
one ``lax.scan`` —; ``m_t = h_t C_t + D ⊙ x̃_t``.  **m is the memory**: it is
exported BEFORE the gate.  The layer's own output is ``W_out(m ⊙ silu(z))``.

**Differential attention** (``window``: key j is seen by query i iff 0 ≤ i − j
< window; ``full``: iff j ≤ i): ``[q ‖ k ‖ v] = W_qkv u + b``, heads of
``head_dim``; adjacent heads pair: q → pairs (q¹, q²), k → pairs (k¹, k²), v →
values V = [v¹ ‖ v²] of 2 · head_dim; a pair of query heads reads key/value
pair ⌊p / group⌋; ``a¹ = softmax(q¹k¹ᵀ/√head_dim) V``, ``a² =
softmax(q²k²ᵀ/√head_dim) V`` (same mask); ``λ = exp(λ_q1·λ_k1) − exp(λ_q2·λ_k2)
+ λ_init``, ``λ_init = 0.8 − 0.6 exp(−0.3 ℓ)``; ``o = (1 − λ_init) ·
RMSNorm(a¹ − λ a²)`` over the pair's 2 · head_dim (one vector of scale a
layer), the pairs flattened, then ``W_o o + b_o``.  The ``full`` layer exports
its k and v (there is no rope to apply).

**GMU**: ``W_out(m ⊙ silu(W_in u))``, no bias, m the memory of the last
Mamba-1.  No mixing over tokens.

**Cross-attention**: ``q = W_q u + b`` alone; k, v are the ``full`` layer's;
the same differential form, causal, full, with the layer's own λ vectors, pair
norm and ``W_o``.

Departures from ``modeling_phi4flash.py``: the fused matrices are stored by
part and (in, out) — ``Wqkv`` as ``wq`` | ``wk`` | ``wv`` (model, heads,
head_dim) with the published head order, ``in_proj`` as ``w_in`` with columns
[x̃ | z], the MLP's ``fc1`` as ``w_gate`` | ``w_up`` —; the convolution is
written as a sum over its taps (``conv1d.weight[c, 0, j]`` is ``conv[j, c]``),
zeros before a sequence's start and no cache; the published code computes ``a¹``
as two flash calls, one a half of V, and concatenates them, which is the one
product with V written here; the state starts at zero and none is returned; no
dropout (the published rates are 0).  Like the system it is given a run of
layers (``cfg.layers``) and the first ``vocab_size`` rows of the embedding, and
reads sizes from the same config and the same flat parameter dict.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: a layer's kind → the stack that holds its mixer's parameters
STACKS = {"mamba": "mamba", "window": "win", "full": "full", "gmu": "gmu", "cross": "cross"}


def kind_of(layer: int, published_layers: int) -> str:
    half = published_layers // 2
    if layer % 2 == 0:
        return "mamba" if layer <= half else "gmu"
    return "window" if layer < half else "full" if layer == half + 1 else "cross"


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w + b


def conv(x, taps, bias):
    """x (B, S, C), taps (K, C): ``y_t = bias + Σ_j taps[j] x_{t−K+1+j}``."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return bias + sum(taps[j] * padded[:, j:j + s] for j in range(k))


def selective_scan(x, dt, a, b, c):
    """x, dt (B, S, C), a (C, N), b and c (B, S, N) → (B, S, C): ``h_t =
    exp(Δ_t ⊗ A) ⊙ h_{t−1} + (Δ_t ⊙ x_t) ⊗ B_t``, ``y_t = h_t C_t``."""
    def token(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[..., None] * a) * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bcn,bn->bc", h, c_t)

    h0 = jnp.zeros((x.shape[0], *a.shape), x.dtype)
    _, y = jax.lax.scan(token, h0, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def mamba(cfg, u, lp):
    """u (B, S, D) normed → (the mixer's output (B, S, D), the memory (B, S,
    d_i))."""
    di, n, r = cfg.d_inner, cfg.d_state, cfg.dt_rank
    xz = u @ lp["w_in"]
    x, z = xz[..., :di], xz[..., di:]
    x = jax.nn.silu(conv(x, lp["conv"], lp["conv_bias"]))
    dbc = x @ lp["w_x"]
    delta, b, c = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
    dt = jax.nn.softplus(delta @ lp["w_dt"] + lp["dt_bias"])
    m = selective_scan(x, dt, -jnp.exp(lp["a_log"]), b, c) + lp["d_skip"] * x
    return (m * jax.nn.silu(z)) @ lp["w_out"], m


def keys_values(cfg, u, lp):
    """(k, v), each (B, kv_heads, S, head_dim), heads in the published order."""
    return tuple(jnp.einsum("bsd,dhk->bhsk", u, lp[w]) + lp[b][:, None, :]
                 for w, b in (("wk", "bk"), ("wv", "bv")))


def differential_attention(cfg, layer, u, lp, k, v, window=None):
    """u (B, S, D) normed, k and v (B, kv_heads, S, head_dim) → (B, S, D)."""
    bsz, s, _ = u.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    group = h // kv
    q = jnp.einsum("bsd,dhk->bhsk", u, lp["wq"]) + lp["bq"][:, None, :]
    q = q.reshape(bsz, h // 2, 2, s, hd)
    k = jnp.repeat(k.reshape(bsz, kv // 2, 2, s, hd), group, axis=1)
    v = v.reshape(bsz, kv // 2, 2, s, hd)
    v = jnp.repeat(jnp.concatenate([v[:, :, 0], v[:, :, 1]], axis=-1), group, axis=1)
    rows, cols = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = cols <= rows if window is None else (cols <= rows) & (rows - cols < window)

    def attend(i):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, i], k[:, :, i]) / math.sqrt(hd)
        return jnp.einsum("bhqk,bhkd->bhqd",
                          jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v)

    lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
           - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + lam_init)
    a = attend(0) - lam * attend(1)  # (B, h / 2, S, 2 hd)
    a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + cfg.norm_eps) * lp["subln"]
    o = jnp.moveaxis((1.0 - lam_init) * a, 1, 2).reshape(bsz, s, h * hd)
    return o @ lp["wo"].reshape(h * hd, -1) + lp["bo"]


def gmu(u, lp, memory):
    return (memory * jax.nn.silu(u @ lp["w_in"])) @ lp["w_out"]


def mlp(u, lp):
    return (jax.nn.silu(u @ lp["w_gate"]) * (u @ lp["w_up"])) @ lp["w_down"]


def _layer_params(params, stack, i):
    """Layer i of a stack."""
    return {k.split(".", 1)[1]: v[i] for k, v in params.items() if k.startswith(stack + ".")}


def run_layers(cfg, params, x, memory=None, k=None, v=None):
    """The layers ``cfg.layers`` on x (B, S, D), with what earlier layers
    exported where these read it → (x, memory, k, v)."""
    nth = dict.fromkeys((*STACKS.values(), "dense"), 0)

    def ln(x, lp):
        return layer_norm(x, lp["norm"], lp["norm_bias"], cfg.norm_eps)

    for layer in cfg.layers:
        kind = kind_of(layer, cfg.published_layers)
        lp = _layer_params(params, STACKS[kind], nth[STACKS[kind]])
        nth[STACKS[kind]] += 1
        u = ln(x, lp)
        if kind == "mamba":
            y, memory = mamba(cfg, u, lp)
        elif kind == "gmu":
            y = gmu(u, lp, memory)
        elif kind == "cross":
            y = differential_attention(cfg, layer, u, lp, k, v)
        else:
            own = keys_values(cfg, u, lp)
            y = differential_attention(cfg, layer, u, lp, *own,
                                       window=cfg.window if kind == "window" else None)
            if kind == "full":
                k, v = own
        x = x + y
        lp = _layer_params(params, "dense", nth["dense"])
        nth["dense"] += 1
        x = x + mlp(ln(x, lp), lp)
    return x, memory, k, v


def forward(cfg, params, tokens):
    """(B, S) → (B, S, V) f32 logits over the held rows."""
    with jax.default_matmul_precision("highest"):
        x, *_ = run_layers(cfg, params, params["embed"][tokens])
        x = layer_norm(x, params["norm_f"], params["norm_f_bias"], cfg.norm_eps)
        return x @ params["embed"].T


def loss(cfg, params, tokens, targets):
    """Mean next-token cross-entropy over targets >= 0."""
    logits = forward(cfg, params, tokens)
    valid = targets >= 0
    gold = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * valid) / jnp.sum(valid)
