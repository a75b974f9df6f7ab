"""Shared model building blocks (pure-functional, no flax).

Params are nested dicts of jnp arrays.  Every dense projection funnels
through ``repro.kernels.ops.gemm`` so tuned Pallas GEMM configs apply to
the whole model zoo.  Norms/softmax run in f32; matmul inputs are cast to
the configured compute dtype.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ops import gemm

__all__ = [
    "dense",
    "init_dense",
    "rmsnorm",
    "layernorm",
    "init_norm",
    "rope_freqs",
    "apply_rope",
    "sinusoidal_positions",
    "causal_attention",
    "chunked_causal_attention",
    "cross_attention",
    "decode_attention",
    "mlp_act",
    "trunc_normal",
]


def scan_or_unroll(use_scan: bool, body, carry, xs):
    """lax.scan when use_scan else a python loop over the leading axis.

    The unrolled path exists for the dry-run depth probes: XLA's
    cost_analysis counts a scan body once regardless of trip count, so
    probe configs unroll to make per-layer costs visible."""
    if use_scan:
        return jax.lax.scan(body, carry, xs)
    leaves = jax.tree_util.tree_leaves(xs)
    length = leaves[0].shape[0]
    ys = []
    for i in range(length):
        x_i = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    else:
        stacked = None
    return carry, stacked


def trunc_normal(key, shape, scale: float, dtype) -> jax.Array:
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (x * scale).astype(dtype)


def init_dense(key, d_in: int, d_out: int, dtype, bias: bool = False) -> dict:
    p = {"w": trunc_normal(key, (d_in, d_out), 1.0 / math.sqrt(d_in), dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: dict, x: jax.Array) -> jax.Array:
    y = gemm(x, p["w"])
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def init_norm(d: int, kind: str, dtype) -> dict:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    # statistics in f32, but cast back to the compute dtype BEFORE the
    # scale multiply: under sequence parallelism the norm output is what
    # crosses the all-gather, and keeping that tensor bf16 halves the
    # collective bytes (measured on yi-6b; see EXPERIMENTS.md §Perf)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return y * p["scale"].astype(x.dtype)


def layernorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    y = y * p["scale"].astype(x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


def norm_apply(p: dict, x: jax.Array, kind: str, eps: float) -> jax.Array:
    return layernorm(p, x, eps) if kind == "layernorm" else rmsnorm(p, x, eps)


# -- positions ----------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,S,1,hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, d: int) -> jax.Array:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / d)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


# -- attention ----------------------------------------------------------------


def _softcap(logits: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return logits
    return cap * jnp.tanh(logits / cap)


def _group_q(q: jax.Array, kv: int) -> jax.Array:
    """(B,S,H,hd) -> (B,S,KV,G,hd): GQA queries grouped by KV head so
    attention contracts against the ORIGINAL K/V — no materialized
    jnp.repeat of the KV tensors (8x memory for kv=8->64 heads)."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, kv, h // kv, hd)


def attention_dispatch(q, k, v, softcap: float = 0.0, chunk_threshold: int = 2048):
    """Policy-aware attention entry point: on a Pallas-enabled deployment
    (kernels/ops.KernelPolicy.use_pallas) long sequences run the Pallas
    flash-attention kernel — with the **tuned** ``(block_q, block_kv)``
    schedule when `launch/tune.py` has recorded one for this
    ``(seq_q, seq_kv, head_dim, dtype)`` workload (see
    ``kernels/ops.flash_schedule``), the built-in heuristic blocks when
    not.  Otherwise the pure-JAX paths below (which are also the
    kernel's correctness oracle)."""
    from repro.kernels.ops import flash_schedule, kernel_policy, note_dispatch

    b, s, h, hd = q.shape
    sk = k.shape[1]
    pol = kernel_policy()
    if (
        pol.use_pallas
        and "flash" in pol.pallas_ops
        and softcap == 0.0
        and s > chunk_threshold
    ):
        from repro.kernels.flash_attention import HEURISTIC_BLOCKS, flash_attention

        tuned = flash_schedule(s, sk, hd, str(q.dtype))
        if tuned is not None:
            note_dispatch("flash", "records")
            return flash_attention(q, k, v, block_q=tuned[0], block_k=tuned[1],
                                   interpret=pol.interpret)
        bq, bk = HEURISTIC_BLOCKS
        if s % bq == 0 and sk % bk == 0:
            note_dispatch("flash", "heuristic")
            return flash_attention(q, k, v, block_q=bq, block_k=bk,
                                   interpret=pol.interpret)
        note_dispatch("flash", "xla")
    if s > chunk_threshold:
        return chunked_causal_attention(q, k, v, softcap=softcap)
    return causal_attention(q, k, v, softcap=softcap)


def causal_attention(q, k, v, softcap: float = 0.0, causal: bool = True):
    """Attention without KV materialized repeat.  q: (B,S,H,hd)
    k/v: (B,Sk,KV,hd)."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    qg = _group_q(q, kv)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    logits = _softcap(logits * (1.0 / math.sqrt(hd)), softcap)
    if causal:
        sk = k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, sq, h, hd)


def chunked_causal_attention(q, k, v, chunk_q: int = 512, chunk_k: int = 1024,
                             softcap: float = 0.0):
    """Flash-style online-softmax attention with O(S·chunk) memory.

    Used automatically for long sequences (prefill_32k) where the full
    (S×S) score tensor would not fit HBM.  lax.scan over KV chunks keeps
    the lowered HLO compact; per-chunk compute is MXU-shaped."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    sk = k.shape[1]
    chunk_q = min(chunk_q, sq)
    chunk_k = min(chunk_k, sk)
    nq, nk = sq // chunk_q, sk // chunk_k
    scale = 1.0 / math.sqrt(hd)

    qc = q.reshape(b, nq, chunk_q, kv, g, hd)
    kc = k.reshape(b, nk, chunk_k, kv, hd)
    vc = v.reshape(b, nk, chunk_k, kv, hd)

    def q_block(iq, q_i):
        # online softmax across kv chunks; q_i: (b, cq, kv, g, hd)
        def kv_step(carry, ik):
            acc, m, l = carry
            k_j = jax.lax.dynamic_index_in_dim(kc, ik, axis=1, keepdims=False)
            v_j = jax.lax.dynamic_index_in_dim(vc, ik, axis=1, keepdims=False)
            logits = jnp.einsum("bqhgd,bkhd->bhgqk", q_i, k_j).astype(jnp.float32)
            logits = _softcap(logits * scale, softcap)
            q_pos = iq * chunk_q + jnp.arange(chunk_q)
            k_pos = ik * chunk_k + jnp.arange(chunk_k)
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask[None, None, None], logits, -1e30)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(q.dtype), v_j
            ).astype(jnp.float32)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, kv, g, chunk_q, hd), jnp.float32)
        m0 = jnp.full((b, kv, g, chunk_q), -1e30, jnp.float32)
        l0 = jnp.zeros((b, kv, g, chunk_q), jnp.float32)
        # only kv chunks that intersect the causal triangle
        last = jnp.minimum(nk - 1, ((iq + 1) * chunk_q - 1) // chunk_k)
        (acc, m, l), _ = jax.lax.scan(
            lambda c, ik: jax.lax.cond(
                ik <= last, lambda: kv_step(c, ik), lambda: (c, None)
            ),
            (acc0, m0, l0),
            jnp.arange(nk),
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        # (b, kv, g, cq, hd) -> (b, cq, kv, g, hd)
        return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)

    outs = jax.lax.map(lambda i: q_block(i, qc[:, i]), jnp.arange(nq))
    # (nq, b, cq, kv, g, hd) -> (b, S, h, hd)
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, h, hd)


def cross_attention(q, k, v, softcap: float = 0.0):
    return causal_attention(q, k, v, softcap=softcap, causal=False)


def decode_attention(q, k_cache, v_cache, length, softcap: float = 0.0,
                     valid_len=None, prefix_len=None):
    """Single-position attention over a cache (no KV repeat).

    q: (B,1,H,hd); k/v_cache: (B,S_max,KV,hd); length: valid prefix len.

    ``valid_len``/``prefix_len`` support bucket-padded prefill (the
    serving engine right-pads prompts to a fixed bucket of length
    ``prefix_len``): cache positions in ``[valid_len[b], prefix_len)``
    hold pad-token K/V and are masked out per sequence; positions at or
    beyond ``prefix_len`` are decode appends and stay governed by
    ``length`` alone."""
    b, sq, h, hd = q.shape
    kv = k_cache.shape[2]
    qg = _group_q(q, kv)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache).astype(jnp.float32)
    logits = _softcap(logits * (1.0 / math.sqrt(hd)), softcap)
    pos = jnp.arange(k_cache.shape[1])
    mask = pos[None, None, None, None, :] < length
    if valid_len is not None:
        real = (pos[None, :] < valid_len[:, None]) | (pos[None, :] >= prefix_len)
        mask = mask & real[:, None, None, None, :]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v_cache)
    return out.reshape(b, sq, h, hd)


# -- MLP activations -------------------------------------------------------------


def mlp_act(kind: str, x: jax.Array, gate: Optional[jax.Array] = None) -> jax.Array:
    if kind == "swiglu":
        assert gate is not None
        return jax.nn.silu(gate) * x
    if kind == "geglu":
        assert gate is not None
        return jax.nn.gelu(gate) * x
    if kind == "squared_relu":
        r = jax.nn.relu(x)
        return r * r
    if kind == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(f"unknown activation {kind}")
