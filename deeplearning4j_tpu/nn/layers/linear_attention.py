"""Gated delta-rule linear attention (Yang, Kautz, Hatamizadeh,
arXiv:2412.06464; the chunked form of arXiv:2406.06484).

A recurrent-state mixer: each head keeps a matrix ``S`` of ``d_v x d_k``
numbers in place of a cache that grows with the sequence, decays it by a
data-dependent ``alpha_t``, and writes ``v_t`` under key ``k_t`` by the
delta rule, which first takes out what the state already answers to that
key::

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

Token by token that is ``T`` sequential rank-one updates. The form below
works in chunks of ``C`` tokens. With ``gamma_t`` the product of the
chunk's decays up to ``t`` and ``u_t = beta_t (v_t - alpha_t S_{t-1} k_t)``
the write that the delta rule makes, the chunk's writes solve the
triangular system

    (I + strict_lower(diag(beta) (K K^T * decay))) U = diag(beta) V
                                        - diag(beta gamma) K S_0^T

(``decay[t, i] = gamma_t / gamma_i``), whose inverse ``T_c`` depends on the
chunk's keys and gates alone: ``W = T_c diag(beta gamma) K`` and
``U_0 = T_c diag(beta) V`` are computed for all chunks at once
(``gdn:chunk_local``), and a ``lax.scan`` over the chunks carries the
state (``gdn:chunk_scan``)::

    U   = U_0 - W S^T
    O   = diag(gamma) Q S^T + (Q K^T * decay) U
    S' = gamma_C S + U^T diag(gamma_C / gamma) K

The state, the decays and the inverse are kept in float32 (float64 under a
float64 gradient check); the matrix products take their operands in the
layer's compute dtype (the input's: bfloat16 under ``PrecisionPolicy
("bf16")``) and accumulate in float32.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import (
    Array, BaseLayerConf, Params, register_layer,
)
from deeplearning4j_tpu.nn.layers.normalization import rms_normalize
from deeplearning4j_tpu.nn.remat import (
    backward_after_cotangent, checkpoint_after_cotangent,
)

#: tokens a chunk: the side of the triangular system, and the MXU's tile
CHUNK = 64
#: rows of the triangular system solved by substitution; larger blocks are
#: put together from these by matrix products
_SUBSTITUTION_ROWS = 16


def _forward_substitution(a: Array) -> Array:
    """``(I + a)^-1`` for strictly lower-triangular ``a [..., n, n]``, row
    by row: ``X_i = e_i - sum_{j<i} a_ij X_j``. Elementwise arithmetic
    only, so float32 stays float32 on an MXU."""
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0], a.shape[:-2] + (n,))]
    for i in range(1, n):
        prev = jnp.stack(rows, axis=-2)                     # [..., i, n]
        rows.append(eye[i] - jnp.sum(a[..., i, :i, None] * prev, axis=-2))
    return jnp.stack(rows, axis=-2)


def unit_lower_inverse(a: Array) -> Array:
    """``(I + a)^-1`` for strictly lower-triangular ``a [..., n, n]``.
    Diagonal blocks of 16 rows by substitution, all at once; then pairs of
    blocks put together, ``[[X1, 0], [-X2 a21 X1, X2]]``, until one is
    left. The intermediate values are entries of the inverse itself, as in
    plain substitution (a Neumann product of powers of ``a`` would cancel
    large terms where keys repeat and ``beta`` is near 2)."""
    n = a.shape[-1]
    b = _SUBSTITUTION_ROWS
    m = n // b
    if n % b or m & (m - 1):
        return _forward_substitution(a)
    lead = a.shape[:-2]
    diag = jnp.stack([a[..., i * b:(i + 1) * b, i * b:(i + 1) * b]
                      for i in range(m)], axis=-3)          # [..., m, b, b]
    inv = _forward_substitution(diag)
    blocks = [inv[..., i, :, :] for i in range(m)]
    mm = lambda x, y: jnp.matmul(x, y, precision=lax.Precision.HIGHEST)
    while len(blocks) > 1:
        merged = []
        for p in range(0, len(blocks), 2):
            x1, x2 = blocks[p], blocks[p + 1]
            a21 = a[..., (p + 1) * b:(p + 2) * b, p * b:(p + 1) * b]
            x21 = -mm(mm(x2, a21), x1)
            top = jnp.concatenate([x1, jnp.zeros(lead + (b, b), a.dtype)], -1)
            merged.append(jnp.concatenate(
                [top, jnp.concatenate([x21, x2], -1)], -2))
        blocks, b = merged, 2 * b
    return blocks[0]


def chunk_local_xla(q: Array, k: Array, v: Array, g: Array, beta: Array, *,
                    compute_dtype) -> tuple:
    """The 64 x 64 work of every chunk as XLA operations: ``q, k [B, H, N,
    C, d_k]``, ``v [B, H, N, C, d_v]``, ``g`` (``log gamma_t``, the running
    sum of ``log alpha`` inside the chunk) and ``beta [B, H, N, C]``.
    Returns ``w, u0, attn, q_in, k_out`` in the order the scan reads them,
    ``[N, B, H, C, .]``. The path of float64 and of every shape the kernels'
    gate refuses, and the reference of the kernels' tests."""
    C = q.shape[-2]
    acc = g.dtype
    cd = jnp.dtype(compute_dtype)

    def mm(spec, x, y):
        return jnp.einsum(spec, x.astype(cd), y.astype(cd),
                          preferred_element_type=acc)

    t = jnp.arange(C)
    diff = g[..., :, None] - g[..., None, :]                # [.., t, i]
    decay = jnp.exp(jnp.where(t[:, None] >= t[None, :], diff, -jnp.inf))
    kk = mm("bhnck,bhndk->bhncd", k, k)
    a = jnp.where(t[:, None] > t[None, :], beta[..., None] * kk * decay, 0.0)
    inv = unit_lower_inverse(a)
    gamma = jnp.exp(g)
    w = mm("bhnct,bhntk->bhnck", inv,
           k.astype(acc) * (beta * gamma)[..., None])
    u0 = mm("bhnct,bhntv->bhncv", inv, v.astype(acc) * beta[..., None])
    attn = mm("bhnck,bhndk->bhncd", q, k) * decay
    q_in = q.astype(acc) * gamma[..., None]
    to_end = jnp.exp(g[..., -1:] - g)                       # gamma_C / gamma
    k_out = k.astype(acc) * to_end[..., None]
    # what the scan keeps for its backward is what it is handed: the
    # products' operands in the compute dtype, u0 wide
    narrow = lambda x: x.astype(cd)
    return tuple(jnp.moveaxis(x, 2, 0) for x in (
        narrow(w), u0, narrow(attn), narrow(q_in), narrow(k_out)))


def gated_delta_rule_chunked(q: Array, k: Array, v: Array, log_alpha: Array,
                             beta: Array, *, chunk_size: int = CHUNK,
                             compute_dtype=None, layer=None) -> Array:
    """The recurrence of the module's docstring from ``S_0 = 0``.

    ``q, k [B, T, H, d_k]``, ``v [B, T, H, d_v]``, ``log_alpha, beta
    [B, T, H]`` (float32 or wider). Returns ``o [B, T, H, d_v]`` in the
    gates' dtype. ``T`` need not be a multiple of ``chunk_size``: the tail
    is padded with tokens that write nothing (``beta = 0``) and decay
    nothing (``log_alpha = 0``).

    The chunk-local work runs in the Pallas kernels of
    ``ops/pallas_delta_rule.py`` where their gate allows (float32 gates, a
    chunk of 64, a block that fits VMEM; ``DL4J_TPU_PALLAS`` not "off") and
    as XLA operations under a checkpoint of their own otherwise; a refusal
    counts under ``layer``'s name, where the caller is a layer."""
    from deeplearning4j_tpu.ops import pallas_delta_rule as pdr
    from deeplearning4j_tpu.ops.pallas_attention import attention_mode
    from deeplearning4j_tpu.ops.pallas_kernels import count_gate_fallback

    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = chunk_size
    N = -(-T // C)
    acc = log_alpha.dtype
    cd = acc if compute_dtype is None else jnp.dtype(compute_dtype)

    mode = attention_mode()
    kernel = mode != "off" and pdr.gdn_chunk_ok(N, dk, dv, C, acc, cd)
    if mode != "off" and not kernel and layer is not None:
        count_gate_fallback(layer, "gdn_chunk_local")
    pdr.count_trace("kernel" if kernel else "xla")
    if kernel:
        N = pdr.padded_chunks(N)        # whole blocks of chunks

    def chunks(x):      # [B, T, H, ...] -> [B, H, N, C, ...]
        x = jnp.pad(x, ((0, 0), (0, N * C - T)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((B, N, C) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    def mm(spec, x, y):
        return jnp.einsum(spec, x.astype(cd), y.astype(cd),
                          preferred_element_type=acc)

    with jax.named_scope("gdn:chunk_local"):
        q, k, v, log_alpha, beta = map(chunks, (q, k, v, log_alpha, beta))
        g = jnp.cumsum(log_alpha, axis=-1)                  # log gamma_t
        if kernel:      # the kernels' own rule: inputs kept, rebuilt in VMEM
            local = backward_after_cotangent(functools.partial(
                pdr.gdn_chunk_local, compute_dtype=cd,
                interpret=mode == "interpret"))(q, k, v, g, beta)
        else:           # the 64 x 64 work, rebuilt backward
            local = checkpoint_after_cotangent(functools.partial(
                chunk_local_xla, compute_dtype=cd))(q, k, v, g, beta)
        end = jnp.moveaxis(jnp.exp(g[..., -1]), 2, 0)       # gamma_C [N, B, H]

    def step(s, xs):    # s [B, H, d_v, d_k]
        w_n, u0_n, attn_n, q_n, k_n, end_n = xs
        u = u0_n - mm("bhck,bhvk->bhcv", w_n, s)
        o = mm("bhck,bhvk->bhcv", q_n, s) + mm("bhcd,bhdv->bhcv", attn_n, u)
        s = end_n[..., None, None] * s + mm("bhcv,bhck->bhvk", u, k_n)
        return s, o

    with jax.named_scope("gdn:chunk_scan"):
        s0 = jnp.zeros((B, H, dv, dk), acc)
        _, o = lax.scan(step, s0, tuple(local) + (end,))
    o = jnp.moveaxis(o, 0, 1)                               # [B, N, H, C, d_v]
    return jnp.moveaxis(o, 2, 3).reshape(B, N * C, H, dv)[:, :T]


def causal_depthwise_conv(x: Array, w: Array) -> Array:
    """``y_t = sum_j w[j] * x_{t-(K-1)+j}`` over time, one filter a channel
    (``x [B, T, F]``, ``w [K, F]``; ``w[K-1]`` meets the current token)."""
    K = w.shape[0]
    T = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + T] * w[j] for j in range(K))


@register_layer
@dataclass
class GatedDeltaNetLayer(BaseLayerConf):
    """The gated delta-rule mixer over ``[B, T, F]``, no bias anywhere:

    ``q~, k~, v = SiLU(conv(W x))`` (a causal depthwise convolution over
    time each); ``q = l2norm(q~) / sqrt(d_k)``, ``k = l2norm(k~)`` by head;
    ``beta = sigmoid(Wb x)``, doubled where ``allow_neg_eigval`` (the
    state's transition may then reflect as well as shrink);
    ``alpha = exp(-exp(A_log) softplus(Wa x + dt_bias))``; the recurrence
    of the module's docstring; then ``Wo`` of the heads'
    ``RMSNorm(o) * gamma * SiLU(Wg x)``.

    Params: ``Wq, Wk [F, H d_k]``, ``Wv, Wg [F, H d_v]``, ``Wa, Wb [F, H]``,
    ``conv_q, conv_k [K, H d_k]``, ``conv_v [K, H d_v]``, ``A_log, dt_bias
    [H]``, ``gamma [d_v]``, ``Wo [H d_v, F]``."""
    n_heads: int = 8
    key_dim: int = 0            # per head; default F // n_heads
    value_dim: int = 0          # per head; default 2 * key_dim
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    norm_eps: float = 1e-6

    def set_n_in(self, in_type: InputType) -> None:
        if in_type.kind != "rnn":
            raise ValueError(
                f"GatedDeltaNetLayer expects RNN input, got {in_type}")
        self.n_in = in_type.size
        if not self.key_dim:
            self.key_dim = max(1, self.n_in // self.n_heads)
        if not self.value_dim:
            self.value_dim = 2 * self.key_dim

    def infer_output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in, in_type.timesteps)

    def param_order(self) -> List[str]:
        return ["Wq", "Wk", "Wv", "Wa", "Wb", "Wg", "conv_q", "conv_k",
                "conv_v", "A_log", "dt_bias", "gamma", "Wo"]

    def regularization(self):
        reg = super().regularization()
        for p in ("A_log", "dt_bias", "conv_q", "conv_k", "conv_v"):
            reg[p] = (self.l1_bias or 0.0, self.l2_bias or 0.0)
        return reg

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        F, H = self.n_in, self.n_heads
        K, V = H * self.key_dim, H * self.value_dim
        ks = jax.random.split(rng, 12)
        lin = lambda key, n_in, n_out: self._init_w(
            key, (n_in, n_out), n_in, n_out, dtype)
        bound = self.conv_kernel ** -0.5      # one input channel a filter
        conv = lambda key, n: jax.random.uniform(
            key, (self.conv_kernel, n), dtype, -bound, bound)
        # a decay rate in [1, 16) and a step in [1e-3, 1e-1], log-uniform,
        # stored through the inverse of softplus (the layer's published
        # implementation does the same)
        dt = jnp.exp(jax.random.uniform(ks[10], (H,), jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        return {
            "Wq": lin(ks[0], F, K), "Wk": lin(ks[1], F, K),
            "Wv": lin(ks[2], F, V), "Wa": lin(ks[3], F, H),
            "Wb": lin(ks[4], F, H), "Wg": lin(ks[5], F, V),
            "conv_q": conv(ks[6], K), "conv_k": conv(ks[7], K),
            "conv_v": conv(ks[8], V),
            "A_log": jnp.log(jax.random.uniform(
                ks[9], (H,), jnp.float32, 1.0, 16.0)).astype(dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "gamma": jnp.ones((self.value_dim,), dtype),
            "Wo": lin(ks[11], V, F),
        }

    def apply(self, params, x, *, state, train, rng, mask=None):
        x = self._dropout_input(x, train, rng)
        if mask is not None:
            x = x * mask[..., None]
        B, T, _ = x.shape
        H, dk, dv = self.n_heads, self.key_dim, self.value_dim
        acc = jnp.promote_types(x.dtype, jnp.float32)
        heads = lambda a, d: a.reshape(B, T, H, d)
        with jax.named_scope("gdn:conv"):
            short = lambda W, c: jax.nn.silu(causal_depthwise_conv(
                x @ params[W], params[c]))
            q = heads(short("Wq", "conv_q"), dk).astype(acc)
            k = heads(short("Wk", "conv_k"), dk).astype(acc)
            v = heads(short("Wv", "conv_v"), dv)
            l2 = lambda a: a * lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + self.norm_eps)
            q, k = l2(q) * dk ** -0.5, l2(k)
        beta = jax.nn.sigmoid((x @ params["Wb"]).astype(acc))
        if self.allow_neg_eigval:
            beta = 2.0 * beta
        log_alpha = -jnp.exp(params["A_log"].astype(acc)) * jax.nn.softplus(
            (x @ params["Wa"]).astype(acc) + params["dt_bias"].astype(acc))
        if mask is not None:        # a masked step writes and decays nothing
            beta = beta * mask[..., None]
            log_alpha = log_alpha * mask[..., None]
        o = gated_delta_rule_chunked(q, k, v, log_alpha, beta,
                                     compute_dtype=x.dtype, layer=self)
        with jax.named_scope("gdn:gate_norm"):
            gate = jax.nn.silu(heads(x @ params["Wg"], dv).astype(acc))
            o = rms_normalize(o, self.norm_eps) * params["gamma"].astype(acc)
            o = (o * gate).astype(x.dtype).reshape(B, T, H * dv)
        out = o @ params["Wo"]
        if mask is not None:
            out = out * mask[..., None]
        return out, state
