"""Plain float32 reference of the dense decoders the benchmark serves.

It follows the published architecture, as the configuration file states
it (``model``): RMSNorm or LayerNorm, rotary positions on the first and
second halves of each head, grouped-query causal attention, a SwiGLU or
squared-ReLU MLP, untied head.  Everything is float32 with matmuls at
``HIGHEST`` precision; the bf16 weights are widened exactly.  It imports
nothing of the program: it reads the weights the benchmark made, by
name.

``control`` makes the control: the same computation with every
projection weight rounded to float8 (e4m3), one scale per tensor.  Attention runs in query blocks and the head in vocabulary
blocks, so that a 4k-token sequence fits beside the served weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["fp8", "logits_at", "served_gaps", "control_gaps"]

_HI = jax.lax.Precision.HIGHEST
_QBLOCK = 512
_VBLOCK = 16384  # at most this many head columns at a time
_FBLOCK = 6144  # at most this many MLP hidden columns at a time


def fp8(x):
    """``x`` in float32, rounded through float8 e4m3 with one scale."""
    x = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x)) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _weight(w, control: bool):
    return fp8(w) if control else w.astype(jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, w, precision=_HI)


def _norm(model: dict, p: dict, x):
    eps = model["norm_eps"]
    scale = p["scale"].astype(jnp.float32)
    if model["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + p["bias"].astype(jnp.float32)


def _rope(x, theta: float):
    """x: (B, T, heads, hd), positions 0..T-1."""
    t, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(model: dict, q, k, v):
    """Causal GQA; q (B, T, H, hd), k/v (B, T, KV, hd)."""
    b, t, h, hd = q.shape
    kv = k.shape[2]
    qb = min(_QBLOCK, t)
    nb = -(-t // qb)
    qp = jnp.pad(q, ((0, 0), (0, nb * qb - t), (0, 0), (0, 0)))
    qp = qp.reshape(b, nb, qb, kv, h // kv, hd)
    kpos = jnp.arange(t)

    def block(i):
        qi = qp[:, i]  # (b, qb, kv, g, hd)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, k, precision=_HI) / math.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=_HI)

    out = jax.lax.map(block, jnp.arange(nb))  # (nb, b, qb, kv, g, hd)
    out = jnp.moveaxis(out, 0, 1).reshape(b, nb * qb, h, hd)
    return out[:, :t]


def _layer(model: dict, control, x, p):
    b, t, d = x.shape
    h, kv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    w = lambda name, sub: _weight(p[name][sub]["w"], control)  # noqa: E731
    y = _norm(model, p["ln1"], x)
    q = _rope(_mm(y, w("attn", "wq")).reshape(b, t, h, hd), model["rope_theta"])
    k = _rope(_mm(y, w("attn", "wk")).reshape(b, t, kv, hd), model["rope_theta"])
    v = _mm(y, w("attn", "wv")).reshape(b, t, kv, hd)
    a = _attention(model, q, k, v).reshape(b, t, h * hd)
    x = x + _mm(a, w("attn", "wo"))
    return x + _mlp(model, control, _norm(model, p["ln2"], x), p["mlp"])


def _mlp(model: dict, control, y, p):
    """The MLP in blocks of its hidden width, so that only a block of
    each weight is widened to float32 at a time."""
    f = p["wi"]["w"].shape[-1]
    fb = _block(f, _FBLOCK)

    def part(i):
        def cols(name):
            w = jax.lax.dynamic_slice_in_dim(p[name]["w"], i * fb, fb, axis=1)
            return _weight(w, control)

        if model["mlp"] == "swiglu":
            hid = jax.nn.silu(_mm(y, cols("wg"))) * _mm(y, cols("wi"))
        elif model["mlp"] == "squared_relu":
            hid = jnp.square(jax.nn.relu(_mm(y, cols("wi"))))
        else:
            raise ValueError(f"unknown mlp {model['mlp']!r}")
        wo = jax.lax.dynamic_slice_in_dim(p["wo"]["w"], i * fb, fb, axis=0)
        return _mm(hid, _weight(wo, control))

    return jax.lax.map(part, jnp.arange(f // fb)).sum(0)


@functools.partial(jax.jit, static_argnames=("model_items", "first", "control"))
def _logits(params, tokens, *, model_items, first: int, control: bool):
    model = dict(model_items)
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(lambda x, p: (_layer(model, control, x, p), None), x, params["layers"])
    y = _norm(model, params["ln_f"], x[:, first:])
    head = params["head"]["w"]
    cols = head.shape[1]
    vb = _block(cols, _VBLOCK)

    def vblock(i):  # blocks divide the columns: no slice is clamped
        wi = jax.lax.dynamic_slice_in_dim(head, i * vb, vb, axis=1)
        return _mm(y, _weight(wi, control))

    out = jax.lax.map(vblock, jnp.arange(cols // vb))  # (nv, b, n, vb)
    out = jnp.moveaxis(out, 0, -2).reshape(*y.shape[:2], cols)
    return out[..., : model["vocab_size"]]


def _block(n: int, cap: int) -> int:
    """The largest divisor of ``n`` within ``cap``."""
    return max(b for b in range(1, min(n, cap) + 1) if n % b == 0)


def logits_at(model: dict, params, tokens, first: int, control: bool = False):
    """Logits (B, T - first, vocab) at positions ``first..T-1`` of
    ``tokens`` (B, T); the head's padded columns are left out."""
    items = tuple(sorted((k, v) for k, v in model.items() if not isinstance(v, (dict, list))))
    return _logits(params, jnp.asarray(tokens, jnp.int32), model_items=items,
                   first=int(first), control=bool(control))


def _teacher_tokens(prompts: np.ndarray, served: np.ndarray) -> np.ndarray:
    """Prompt plus every served token but the last: position P-1+i of it
    predicts served token i."""
    return np.concatenate([prompts, served[:, :-1]], axis=1).astype(np.int32)


def served_gaps(model: dict, params, prompts: np.ndarray, served: np.ndarray) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position: (B, g), 0 where the served token
    is the reference's argmax."""
    ref = logits_at(model, params, _teacher_tokens(prompts, served), prompts.shape[1] - 1)
    picked = jnp.take_along_axis(ref, jnp.asarray(served)[..., None], -1)[..., 0]
    return np.asarray(jnp.max(ref, -1) - picked)


def control_gaps(model: dict, params, prompts: np.ndarray, served: np.ndarray) -> np.ndarray:
    """The control: at the same prompts and tokens, the gap of the token
    that the float8-weight reference puts first, against the float32 one."""
    tokens = _teacher_tokens(prompts, served)
    first = prompts.shape[1] - 1
    ref = logits_at(model, params, tokens, first)
    low = logits_at(model, params, tokens, first, control=True)
    top = jnp.argmax(low, -1)
    picked = jnp.take_along_axis(ref, top[..., None], -1)[..., 0]
    return np.asarray(jnp.max(ref, -1) - picked)
