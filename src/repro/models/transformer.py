"""Transformer model zoo: dense GQA decoders, MoE decoders, VLM backbones
(stub frontend), and encoder–decoder (whisper family).

Functional style: ``init_params(cfg, key)`` builds a pytree of arrays
(layers stacked on a leading axis so the forward pass can
``lax.scan`` over them — this keeps the lowered HLO size independent of
depth, which is what makes 80–95-layer dry-runs compile fast);
``loss_fn`` / ``prefill`` / ``decode_step`` are pure functions of
(cfg, params, batch).  Sharding is injected via
``repro.dist.api.constrain`` (no-op outside a mesh context).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.api import constrain, logical
from repro.kernels.ops import StackedWeight
from repro.models import common as cm

__all__ = [
    "init_params",
    "loss_fn",
    "lm_loss_from_logits",
    "forward_logits",
    "prefill",
    "decode_step",
    "init_cache",
]


def _dt(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


# =============================================================================
# per-block params
# =============================================================================


def init_attn(key, cfg: ArchConfig, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    dt = _dt(cfg)
    ks = jax.random.split(key, 4)
    return {
        "wq": cm.init_dense(ks[0], d, h * hd, dt, bias=cfg.qkv_bias),
        "wk": cm.init_dense(ks[1], d, kv * hd, dt, bias=cfg.qkv_bias),
        "wv": cm.init_dense(ks[2], d, kv * hd, dt, bias=cfg.qkv_bias),
        "wo": cm.init_dense(ks[3], h * hd, d, dt),
    }


def init_mlp(key, cfg: ArchConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    dt = _dt(cfg)
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind in ("swiglu", "geglu"):
        return {
            "wi": cm.init_dense(ks[0], d, f, dt),
            "wg": cm.init_dense(ks[1], d, f, dt),
            "wo": cm.init_dense(ks[2], f, d, dt),
        }
    return {
        "wi": cm.init_dense(ks[0], d, f, dt),
        "wo": cm.init_dense(ks[2], f, d, dt),
    }


def init_moe(key, cfg: ArchConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = _dt(cfg)
    ks = jax.random.split(key, 4)
    gated = cfg.mlp_kind in ("swiglu", "geglu")
    p = {
        "router": {"w": cm.trunc_normal(ks[0], (d, e), 1.0 / math.sqrt(d), jnp.float32)},
        "wi": cm.trunc_normal(ks[1], (e, d, f), 1.0 / math.sqrt(d), dt),
        "wo": cm.trunc_normal(ks[3], (e, f, d), 1.0 / math.sqrt(f), dt),
    }
    if gated:
        p["wg"] = cm.trunc_normal(ks[2], (e, d, f), 1.0 / math.sqrt(d), dt)
    return p


def init_block(key, cfg: ArchConfig, moe: bool, cross: bool = False) -> dict:
    ks = jax.random.split(key, 5)
    d = cfg.d_model
    dt = _dt(cfg)
    p = {
        "ln1": cm.init_norm(d, cfg.norm, dt),
        "attn": init_attn(ks[0], cfg),
        "ln2": cm.init_norm(d, cfg.norm, dt),
        "mlp": init_moe(ks[1], cfg) if moe else init_mlp(ks[1], cfg),
    }
    if cross:
        p["ln_cross"] = cm.init_norm(d, cfg.norm, dt)
        p["cross"] = init_attn(ks[2], cfg, cross=True)
    return p


# =============================================================================
# block application
# =============================================================================


def attn_apply(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    causal: bool = True,
    cross: bool = False,
    kv_cache: Optional[dict] = None,
    cache_len: Optional[jax.Array] = None,
    xkv: Optional[jax.Array] = None,
    valid_len: Optional[jax.Array] = None,
    prefix_len: Optional[jax.Array] = None,
):
    """Self- or cross-attention.  Returns (out, new_kv | None).

    self, no cache:   keys/values from x (train / prefill)
    self, cache:      decode — append (B,1) K/V at cache_len, attend prefix
    cross, no cache:  keys/values from xkv = encoder output
    cross, cache:     decode — attend precomputed encoder K/V in cache"""
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = cm.dense(p["wq"], x).reshape(b, s, h, hd)
    if cross and kv_cache is not None:
        if cfg.pos_embed == "rope":
            pass  # no rope on cross-attention queries (whisper family)
        out = cm.cross_attention(q, kv_cache["k"], kv_cache["v"], softcap=cfg.attn_softcap)
        return cm.dense(p["wo"], out.reshape(b, s, h * hd)), None

    src = x if xkv is None else xkv
    k = cm.dense(p["wk"], src).reshape(b, src.shape[1], kvh, hd)
    v = cm.dense(p["wv"], src).reshape(b, src.shape[1], kvh, hd)
    if cfg.pos_embed == "rope" and not cross:
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k = cm.apply_rope(k, positions, cfg.rope_theta)
    # NOTE: no manual q/k constraints — over-constraining forced
    # replicated-K layouts whose backward all-reduced (T, d) f32 grads
    # every layer; GSPMD propagates head sharding from the weights.

    new_kv = None
    if cross:
        out = cm.cross_attention(q, k, v, softcap=cfg.attn_softcap)
    elif kv_cache is not None:  # self-attention decode: append to cache
        kc = jax.lax.dynamic_update_slice_in_dim(kv_cache["k"], k, cache_len, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(kv_cache["v"], v, cache_len, axis=1)
        kc = constrain(kc, logical(None, "kv_seq", None, None) if b == 1 else logical("dp", None, None, None))
        vc = constrain(vc, logical(None, "kv_seq", None, None) if b == 1 else logical("dp", None, None, None))
        new_kv = {"k": kc, "v": vc}
        out = cm.decode_attention(q, kc, vc, cache_len + s, softcap=cfg.attn_softcap,
                                  valid_len=valid_len, prefix_len=prefix_len)
    else:
        if not causal:
            out = cm.cross_attention(q, k, v, softcap=cfg.attn_softcap)
        else:
            out = cm.attention_dispatch(
                q, k, v, softcap=cfg.attn_softcap,
                chunk_threshold=cfg.attn_chunk_threshold,
            )
        # the cached copies are sequence-sharded like the prefill cache
        new_kv = {
            "k": constrain(k, logical("dp", "sp", None, None)),
            "v": constrain(v, logical("dp", "sp", None, None)),
        }
    return cm.dense(p["wo"], out.reshape(b, s, h * hd)), new_kv


def mlp_apply(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.mlp_kind in ("swiglu", "geglu"):
        hidden = cm.mlp_act(cfg.mlp_kind, cm.dense(p["wi"], x), cm.dense(p["wg"], x))
    else:
        hidden = cm.mlp_act(cfg.mlp_kind, cm.dense(p["wi"], x))
    return cm.dense(p["wo"], hidden)


def _moe_route(cfg: ArchConfig, p: dict, xf: jax.Array):
    """Router: top-k experts + weights + aux losses (global, tiny)."""
    e, k = cfg.n_experts, cfg.experts_per_token
    router_logits = jnp.einsum(
        "td,de->te", xf.astype(jnp.float32), p["router"]["w"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    if cfg.router_norm_topk:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    density = jnp.mean(jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0)
    density_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * density_prob)
    zloss = jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)
    return top_e, top_w, 0.01 * aux + 1e-3 * zloss


def _sorted_capacity_buffers(t: int, e: int, cap: int, k: int, top_e, top_w):
    """Sorted-dispatch bookkeeping shared by both MoE impls.  Returns
    (buf_tok (e,cap), buf_valid (e,cap), inv (t,k) slot-or--1)."""
    flat_e = top_e.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(t * k) - starts[sorted_e]
    keep = pos_in_e < cap
    slot = sorted_e * cap + jnp.where(keep, pos_in_e, 0)
    buf_tok = jnp.zeros((e * cap,), jnp.int32).at[slot].set(
        jnp.where(keep, sorted_tok, 0)
    )
    buf_valid = jnp.zeros((e * cap,), bool).at[slot].max(keep)
    inv = jnp.full((t * k,), -1, jnp.int32).at[order].set(jnp.where(keep, slot, -1))
    return buf_tok.reshape(e, cap), buf_valid.reshape(e, cap), inv.reshape(t, k)


def _expert_ffn(cfg: ArchConfig, p_or_weights, xe):
    wi = p_or_weights["wi"]
    wo = p_or_weights["wo"]
    if "wg" in p_or_weights:
        hid = cm.mlp_act(
            cfg.mlp_kind,
            jnp.einsum("ecd,edf->ecf", xe, wi),
            jnp.einsum("ecd,edf->ecf", xe, p_or_weights["wg"]),
        )
    else:
        hid = cm.mlp_act(cfg.mlp_kind, jnp.einsum("ecd,edf->ecf", xe, wi))
    return hid, wo


def moe_apply_a2a(cfg: ArchConfig, p: dict, x: jax.Array, mesh, rules):
    """Expert dispatch/combine with EXPLICIT all-to-all under shard_map.

    Pure-GSPMD dispatch gathers index across shards, which the partitioner
    lowers by REPLICATING the (T_global, d) token buffer (17 GB/device on
    qwen3 — measured, §Perf cell 2).  Here every device routes its LOCAL
    tokens into per-expert send buffers, one all-to-all over the model
    axis delivers them to the expert owners, the expert FFN runs with
    FSDP-gathered weights, and the reverse all-to-all brings results home.
    shard_map collectives are differentiable (all_to_all^T = all_to_all,
    all_gather^T = psum_scatter), so the same code serves training.

    The shard_map runs on the mesh's devices with every axis ``Auto``
    (GSPMD propagation, which ``constrain`` annotates for): under
    ``Explicit`` axes, as ``jax.make_mesh`` gives by default, the
    token-sharded (T, d) result could not be reshaped back to
    (B, S, d)."""
    from jax.sharding import AxisType
    from jax.sharding import PartitionSpec as P

    mesh = mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))

    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.experts_per_token
    xf = x.reshape(t, d)
    top_e, top_w, aux_total = _moe_route(cfg, p, xf)

    dp_ax = rules.get("dp")
    dp_axes = dp_ax if isinstance(dp_ax, tuple) else (dp_ax,)
    dp_axes = tuple(a for a in dp_axes if a in mesh.shape)
    tok_axes = dp_axes + ("model",)
    n_tok_shards = 1
    for a in tok_axes:
        n_tok_shards *= mesh.shape[a]
    m_size = mesh.shape["model"]
    t_dev = t // n_tok_shards
    gated = "wg" in p
    # few-expert case (grok: 8 experts < 16-way model axis): r model
    # shards co-own each expert; capacity splits across the replicas
    r = 1 if e % m_size == 0 else m_size // e
    cap_dev = max(r, int(k * t_dev * cfg.moe_capacity_factor / e))
    cap_dev = ((cap_dev + r - 1) // r) * r  # divisible by the replica count

    def local(xf_l, te_l, tw_l, wi_l, wg_l, wo_l):
        # xf_l: (t_dev, d); te/tw: (t_dev, k)
        buf_tok, buf_valid, inv = _sorted_capacity_buffers(
            t_dev, e, cap_dev, k, te_l, tw_l
        )
        send = xf_l[buf_tok] * buf_valid[..., None].astype(xf_l.dtype)  # (e,cap,d)
        if r > 1:
            send = send.reshape(e * r, cap_dev // r, d)
        recv = jax.lax.all_to_all(
            send, "model", split_axis=0, concat_axis=1, tiled=True
        )  # e>=m: (e/m, cap_dev*m, d);  e<m: (1, (cap_dev//r)*m, d)

        if r > 1:
            # this device owns expert (model_index // r): slice, then
            # FSDP-gather only that expert's weights over dp
            e_idx = jax.lax.axis_index("model") // r
            def slice_gather(w):  # (e, d/dp, f) -> (d, f)
                we = jax.lax.dynamic_index_in_dim(w, e_idx, 0, keepdims=False)
                return jax.lax.all_gather(we, dp_axes, axis=0, tiled=True)
            wi_f, wo_f = slice_gather(wi_l), slice_gather(wo_l)
            tok = recv.reshape(-1, d)
            hid_in = tok @ wi_f
            if gated:
                hid = cm.mlp_act(cfg.mlp_kind, hid_in, tok @ slice_gather(wg_l))
            else:
                hid = cm.mlp_act(cfg.mlp_kind, hid_in)
            ye = (hid @ wo_f).reshape(*recv.shape[:-1], d)
        else:
            wi_f = jax.lax.all_gather(wi_l, dp_axes, axis=1, tiled=True)
            wo_f = jax.lax.all_gather(wo_l, dp_axes, axis=1, tiled=True)
            weights = {"wi": wi_f, "wo": wo_f}
            if gated:  # static: ungated models never gather wg_l
                weights["wg"] = jax.lax.all_gather(wg_l, dp_axes, axis=1, tiled=True)
            hid, wo_full = _expert_ffn(cfg, weights, recv)
            ye = jnp.einsum("ecf,efd->ecd", hid, wo_full)

        back = jax.lax.all_to_all(
            ye, "model", split_axis=1, concat_axis=0, tiled=True
        )
        flat = back.reshape(e * cap_dev, d)
        gathered = flat[inv.clip(0)] * (inv >= 0)[..., None].astype(flat.dtype)
        return jnp.einsum("tkd,tk->td", gathered, tw_l.astype(flat.dtype))

    tok_spec = P(tok_axes, None)
    # e >= m: experts sharded on model; e < m: experts replicated on model
    w_spec = P("model", dp_axes, None) if r == 1 else P(None, dp_axes, None)
    in_specs = [tok_spec, P(tok_axes, None), P(tok_axes, None), w_spec, w_spec, w_spec]
    wg = p.get("wg")
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=tok_spec,
        check_vma=False,
    )
    out = fn(xf, top_e, top_w, p["wi"], wg if wg is not None else p["wi"], p["wo"])
    # (when ungated, wg input is a dummy alias; `local` ignores it)
    return out.reshape(b, s, d), aux_total


def moe_apply(cfg: ArchConfig, p: dict, x: jax.Array):
    """Top-k routed MoE with capacity buffers (GShard/Switch-style sorted
    dispatch — O(T·k) memory, expert-parallel friendly).

    Returns (out, aux_loss)."""
    from repro.dist.api import current_mesh, current_rules

    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.experts_per_token
    mesh = current_mesh()
    if (
        cfg.moe_impl == "a2a"
        and mesh is not None
        and "model" in mesh.shape
        and (e % mesh.shape["model"] == 0 or mesh.shape["model"] % e == 0)
        and t % mesh.devices.size == 0
    ):
        return moe_apply_a2a(cfg, p, x, mesh, current_rules())
    xf = x.reshape(t, d)

    router_logits = jnp.einsum(
        "td,de->te", xf.astype(jnp.float32), p["router"]["w"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)  # (t, k)
    if cfg.router_norm_topk:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # -- load-balance aux (Switch) + router z-loss ---------------------------
    density = jnp.mean(jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0)
    density_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * density_prob)
    zloss = jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)
    aux_total = 0.01 * aux + 1e-3 * zloss

    # -- sorted capacity dispatch --------------------------------------------
    cap = max(1, int(k * t * cfg.moe_capacity_factor / e))
    flat_e = top_e.reshape(-1)  # (t*k,)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]
    # position of each entry within its expert group
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(t * k) - starts[sorted_e]
    keep = pos_in_e < cap
    slot = sorted_e * cap + jnp.where(keep, pos_in_e, 0)

    # gather tokens into (e, cap, d) buffers; the INDEX buffers are
    # sharded (expert, capacity) FIRST so the gather executes shard-local
    # (the all-to-all of token rows is the dispatch collective) instead of
    # materializing a replicated (e, cap, d) — which cost 32 GB/device
    buf_tok = jnp.full((e * cap,), 0, jnp.int32)
    buf_valid = jnp.zeros((e * cap,), bool)
    buf_tok = buf_tok.at[slot].set(jnp.where(keep, sorted_tok, 0))
    buf_valid = buf_valid.at[slot].max(keep)
    buf_tok2 = constrain(buf_tok.reshape(e, cap), logical("expert", "expert_cap"))
    buf_valid2 = constrain(buf_valid.reshape(e, cap), logical("expert", "expert_cap"))
    xe = xf[buf_tok2] * buf_valid2[..., None].astype(xf.dtype)
    xe = constrain(xe, logical("expert", "expert_cap", None))

    # expert FFN (batched einsum over the expert dim)
    if "wg" in p:
        hid = cm.mlp_act(
            cfg.mlp_kind,
            jnp.einsum("ecd,edf->ecf", xe, p["wi"]),
            jnp.einsum("ecd,edf->ecf", xe, p["wg"]),
        )
    else:
        hid = cm.mlp_act(cfg.mlp_kind, jnp.einsum("ecd,edf->ecf", xe, p["wi"]))
    hid = constrain(hid, logical("expert", "expert_cap", "expert_ffn"))
    ye = constrain(
        jnp.einsum("ecf,efd->ecd", hid, p["wo"]),
        logical("expert", "expert_cap", None),
    )

    # combine back as a token-sharded GATHER (a scatter-add here makes
    # GSPMD replicate the full (t, d) accumulator — 25 GB/dev on grok);
    # inv[t, j] = slot of (token t, choice j), -1 if dropped
    inv = jnp.full((t * k,), -1, jnp.int32)
    inv = inv.at[order].set(jnp.where(keep, slot, -1))
    inv2 = constrain(inv.reshape(t, k), logical("dp", None))
    w2 = constrain(top_w.astype(ye.dtype), logical("dp", None))
    gathered = ye.reshape(e * cap, d)[inv2.clip(0)]  # (t, k, d)
    gathered = gathered * (inv2 >= 0)[..., None].astype(ye.dtype) * w2[..., None]
    out = gathered.sum(axis=1)
    return out.reshape(b, s, d), aux_total


def block_apply(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    moe: bool,
    causal: bool = True,
    kv_cache: Optional[dict] = None,
    cache_len=None,
    cross_kv: Optional[dict] = None,
    enc_out: Optional[jax.Array] = None,
    valid_len=None,
    prefix_len=None,
):
    """One transformer block.  Returns (x, new_kv, aux)."""
    h = constrain(cm.norm_apply(p["ln1"], x, cfg.norm, cfg.norm_eps),
                  logical("dp", "sp", None))
    a, new_kv = attn_apply(
        cfg, p["attn"], h, positions, causal=causal, kv_cache=kv_cache,
        cache_len=cache_len, valid_len=valid_len, prefix_len=prefix_len,
    )
    a = constrain(a, logical("dp", "sp", None))  # reduce-scatter into seq shards
    x = x + a
    if "cross" in p:
        h = cm.norm_apply(p["ln_cross"], x, cfg.norm, cfg.norm_eps)
        c, _ = attn_apply(
            cfg, p["cross"], h, positions, cross=True,
            kv_cache=cross_kv, cache_len=cache_len, xkv=enc_out,
        )
        x = x + c
    h = constrain(cm.norm_apply(p["ln2"], x, cfg.norm, cfg.norm_eps),
                  logical("dp", "sp", None))
    aux = jnp.zeros((), jnp.float32)
    if moe:
        m, aux = moe_apply(cfg, p["mlp"], h)
    else:
        m = mlp_apply(cfg, p["mlp"], h)
    m = constrain(m, logical("dp", "sp", None))
    x = x + m
    x = constrain(x, logical("dp", "sp", None))
    return x, new_kv, aux


# =============================================================================
# full models
# =============================================================================


def init_params(cfg: ArchConfig, key) -> dict:
    dt = _dt(cfg)
    ks = jax.random.split(key, 8)
    v, d = cfg.padded_vocab, cfg.d_model
    moe = cfg.family == "moe"
    p: dict = {
        "embed": {"table": cm.trunc_normal(ks[0], (v, d), d ** -0.5, dt)},
        "ln_f": cm.init_norm(d, cfg.norm, dt),
    }
    if not cfg.tie_embeddings:
        p["head"] = {"w": cm.trunc_normal(ks[1], (d, v), 1.0 / math.sqrt(d), dt)}

    cross = cfg.family == "encdec"
    layer_keys = jax.random.split(ks[2], cfg.n_layers)
    p["layers"] = jax.vmap(lambda k: init_block(k, cfg, moe=moe, cross=cross))(layer_keys)

    if cfg.family == "encdec":
        enc_keys = jax.random.split(ks[3], cfg.n_encoder_layers)
        p["encoder"] = {
            "layers": jax.vmap(lambda k: init_block(k, cfg, moe=False))(enc_keys),
            "ln_f": cm.init_norm(d, cfg.norm, dt),
        }
    if cfg.pos_embed == "learned":
        p["pos_table"] = cm.trunc_normal(ks[4], (32768, d), 0.02, dt)
    return p


def _remat(cfg: ArchConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


def _scan_blocks(cfg: ArchConfig, layers, x, positions, *, moe, causal=True,
                 enc_out=None, collect_kv=False):
    """lax.scan over the stacked layer params."""

    def body(carry, layer_p):
        x, aux = carry
        x2, kv, a = block_apply(
            cfg, layer_p, x, positions, moe=moe, causal=causal, enc_out=enc_out
        )
        ys = kv if collect_kv else None
        return (x2, aux + a), ys

    body = _remat(cfg, body)
    (x, aux), kvs = cm.scan_or_unroll(
        cfg.scan_layers, body, (x, jnp.zeros((), jnp.float32)), layers
    )
    return x, aux, kvs


def embed_tokens(cfg: ArchConfig, params: dict, tokens: jax.Array) -> jax.Array:
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    return constrain(x, logical("dp", "sp", None))


def lm_logits(cfg: ArchConfig, params: dict, x: jax.Array) -> jax.Array:
    x = cm.norm_apply(params["ln_f"], x, cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embed"]["table"].T
    else:
        w = params["head"]["w"]
    from repro.kernels.ops import gemm

    logits = gemm(x, w).astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    return constrain(logits, logical("dp", None, "tp"))


def _encode(cfg: ArchConfig, params: dict, frames: jax.Array):
    """Whisper-family encoder over precomputed frame embeddings (conv
    frontend is a stub per the assignment)."""
    x = frames.astype(jnp.dtype(cfg.compute_dtype))
    x = x + cm.sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)
    pos = jnp.arange(x.shape[1])[None, :]
    x, _, _ = _scan_blocks(cfg, params["encoder"]["layers"], x, pos, moe=False, causal=False)
    return cm.norm_apply(params["encoder"]["ln_f"], x, cfg.norm, cfg.norm_eps)


def forward_hidden(cfg: ArchConfig, params: dict, batch: dict):
    """Training/prefill forward to the FINAL HIDDEN states (pre ln_f).
    batch:
      tokens (B, S_text) int32
      [frontend_embeds (B, S_front, d)]   vlm patch / audio frame stub
      [enc_frames (B, S_enc, d)]          encdec encoder input
    Returns (x (B, S, d), aux_loss)."""
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    if cfg.frontend != "none" and "frontend_embeds" in batch:
        fe = batch["frontend_embeds"].astype(x.dtype)
        x = jnp.concatenate([fe, x], axis=1)
    positions = jnp.arange(x.shape[1])[None, :]
    if cfg.pos_embed == "learned":
        x = x + jnp.take(params["pos_table"], positions[0] % params["pos_table"].shape[0], axis=0)
    enc_out = None
    if cfg.family == "encdec":
        enc_out = _encode(cfg, params, batch["enc_frames"])
    moe = cfg.family == "moe"
    x, aux, _ = _scan_blocks(
        cfg, params["layers"], x, positions, moe=moe, enc_out=enc_out
    )
    return x, aux


def forward_logits(cfg: ArchConfig, params: dict, batch: dict):
    x, aux = forward_hidden(cfg, params, batch)
    return lm_logits(cfg, params, x), aux


def lm_loss_from_logits(cfg: ArchConfig, logits: jax.Array, aux: jax.Array,
                        labels: jax.Array):
    """Cross-entropy (+ MoE aux, + z-loss).  labels -1 = masked.  Shared
    across all families (dense/moe/ssm/hybrid/encdec/vlm)."""
    if logits.shape[1] != labels.shape[1]:  # vlm frontend positions are unsupervised
        pad = logits.shape[1] - labels.shape[1]
        labels = jnp.concatenate(
            [jnp.full((labels.shape[0], pad), -1, labels.dtype), labels], axis=1
        )
    valid = labels >= 0
    lab = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(valid.sum(), 1)
    ce = -jnp.sum(jnp.where(valid, ll, 0.0)) / denom
    zloss = 1e-4 * jnp.sum(jnp.where(valid, jax.nn.logsumexp(logits, -1) ** 2, 0.0)) / denom
    loss = ce + zloss + aux
    metrics = {
        "loss": loss,
        "ce": ce,
        "aux": aux,
        "tokens": valid.sum(),
        "accuracy": jnp.sum(jnp.where(valid, (jnp.argmax(logits, -1) == lab), 0)) / denom,
    }
    return loss, metrics


def streaming_lm_loss(cfg: ArchConfig, params: dict, x: jax.Array,
                      labels: jax.Array, aux: jax.Array,
                      chunk: int = 512):
    """CE + z-loss WITHOUT materializing (B, S, V) logits: scan over
    sequence chunks, each chunk computing its own logits -> per-token
    loss pieces.  Cuts the dominant train-step temp buffer (the f32
    logits were ~10 GB/device at 4k x 256 x 150k-vocab) to
    (B, chunk, V) with the chunk body rematerialized in backward."""
    x = cm.norm_apply(params["ln_f"], x, cfg.norm, cfg.norm_eps)
    w = params["embed"]["table"].T if cfg.tie_embeddings else params["head"]["w"]
    b, s, d = x.shape
    chunk = min(chunk, s)
    if s % chunk:
        chunk = s  # fallback: odd lengths take the unchunked path
    n_chunks = s // chunk
    xc = x.reshape(b, n_chunks, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_body(carry, inp):
        ce_sum, z_sum, acc_sum, n_valid = carry
        xi, li = inp  # (b, chunk, d), (b, chunk)
        from repro.kernels.ops import gemm

        logits = gemm(xi, w).astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(pad_mask, -1e30, logits)
        logits = constrain(logits, logical("dp", None, "tp"))
        valid = li >= 0
        lab = jnp.where(valid, li, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        ce = jnp.sum(jnp.where(valid, lse - picked, 0.0))
        zl = jnp.sum(jnp.where(valid, lse**2, 0.0))
        acc = jnp.sum(jnp.where(valid, jnp.argmax(logits, -1) == lab, 0))
        return (ce_sum + ce, z_sum + zl, acc_sum + acc, n_valid + valid.sum()), None

    init = (jnp.zeros(()), jnp.zeros(()), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32))
    (ce_sum, z_sum, acc_sum, n_valid), _ = jax.lax.scan(chunk_body, init, (xc, lc))
    denom = jnp.maximum(n_valid, 1)
    ce = ce_sum / denom
    zloss = 1e-4 * z_sum / denom
    loss = ce + zloss + aux
    metrics = {
        "loss": loss,
        "ce": ce,
        "aux": aux,
        "tokens": n_valid,
        "accuracy": acc_sum / denom,
    }
    return loss, metrics


def loss_fn(cfg: ArchConfig, params: dict, batch: dict):
    logits, aux = forward_logits(cfg, params, batch)
    return lm_loss_from_logits(cfg, logits, aux, batch["labels"])


# =============================================================================
# serving: prefill + decode
# =============================================================================


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None) -> dict:
    dt = dtype or jnp.dtype(cfg.compute_dtype)
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (cfg.n_layers, batch, max_len, kvh, hd)
    cache = {
        "k": jnp.zeros(shape, dt),
        "v": jnp.zeros(shape, dt),
        "len": jnp.zeros((), jnp.int32),
    }
    if cfg.family == "encdec":
        eshape = (cfg.n_layers, batch, cfg.encoder_len, kvh, hd)
        cache["cross_k"] = jnp.zeros(eshape, dt)
        cache["cross_v"] = jnp.zeros(eshape, dt)
    return cache


def prefill(cfg: ArchConfig, params: dict, batch: dict, max_len: int,
            last_idx: Optional[jax.Array] = None):
    """Run the prompt, return (last_logits, cache).

    ``last_idx`` (B,) int32, optional: per-sequence index of the last
    *real* token along the final sequence axis.  The serving engine
    right-pads prompts into fixed buckets, so the logits that seed
    decoding must come from each sequence's own last real position, not
    the bucket's final column.  None keeps the legacy behavior (all
    sequences end at the last column)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = embed_tokens(cfg, params, tokens)
    if cfg.frontend != "none" and "frontend_embeds" in batch:
        x = jnp.concatenate([batch["frontend_embeds"].astype(x.dtype), x], axis=1)
    positions = jnp.arange(x.shape[1])[None, :]
    if cfg.pos_embed == "learned":
        x = x + jnp.take(params["pos_table"], positions[0] % params["pos_table"].shape[0], axis=0)
    enc_out = None
    if cfg.family == "encdec":
        enc_out = _encode(cfg, params, batch["enc_frames"])
    moe = cfg.family == "moe"
    x, _, kvs = _scan_blocks(
        cfg, params["layers"], x, positions, moe=moe, enc_out=enc_out, collect_kv=True
    )
    if last_idx is None:
        x_last = x[:, -1:, :]
    else:
        idx = last_idx.astype(jnp.int32)[:, None, None]  # (B,1,1) -> bcast over d
        x_last = jnp.take_along_axis(x, idx, axis=1)
    logits = lm_logits(cfg, params, x_last)
    # build the fixed-size cache from collected per-layer K/V
    cache = init_cache(cfg, b, max_len)
    seq = x.shape[1]
    cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], kvs["k"], 0, axis=2)
    cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], kvs["v"], 0, axis=2)
    cache["len"] = jnp.asarray(seq, jnp.int32)
    if cfg.family == "encdec":
        # precompute cross K/V per layer from encoder output
        def cross_kv(layer_p):
            kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
            k = cm.dense(layer_p["cross"]["wk"], enc_out)
            v = cm.dense(layer_p["cross"]["wv"], enc_out)
            bsz, es = enc_out.shape[:2]
            return k.reshape(bsz, es, kvh, hd), v.reshape(bsz, es, kvh, hd)

        ck, cv = jax.lax.map(cross_kv, params["layers"])
        cache["cross_k"], cache["cross_v"] = ck, cv
    return logits, cache


#: the per-layer groups of dense projections ({name: {"w", ["b"]}}), which
#: a decode step reads in place from the stacked params
_DENSE_GROUPS = ("attn", "cross", "mlp")


def _decode_layer(layers: dict, li: jax.Array, moe: bool) -> dict:
    """Layer ``li`` of the stacked params for one decode step.  Each dense
    projection's weight stays in its stack as an ``ops.StackedWeight``,
    which the GEMM reads in place; everything else (norms, biases, the
    router and expert weights) is sliced out."""

    def take(a):
        return jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False)

    def dense(p):
        out = {"w": StackedWeight(p["w"], li)}
        if "b" in p:
            out["b"] = take(p["b"])
        return out

    return {
        name: (
            {proj: dense(p) for proj, p in group.items()}
            if name in _DENSE_GROUPS and not (moe and name == "mlp")
            else jax.tree_util.tree_map(take, group)
        )
        for name, group in layers.items()
    }


def decode_step(cfg: ArchConfig, params: dict, cache: dict, tokens: jax.Array):
    """One token for every sequence.  tokens: (B, 1).  Returns
    (logits (B,1,V), new_cache).

    The stacked (L, ...) KV cache rides in the scan CARRY and each layer
    updates its slice in place (dynamic_update_index) — XLA's while-loop
    state aliasing then keeps ONE cache buffer live instead of the
    xs+ys pair a scan-over-cache would hold (2x cache = 10.7 GB/device
    on qwen2-72b decode_32k).  The dense projections' weights are not
    scanned: each GEMM reads its layer from the stacked params in place
    (``_decode_layer``), so no layer is copied out before its kernel."""
    b = tokens.shape[0]
    x = embed_tokens(cfg, params, tokens)
    pos = cache["len"]
    # bucket-padded serving: the engine stashes per-sequence real prompt
    # lengths (+ the bucket width) in the cache so pad K/V rows are
    # masked out of every decode step (see cm.decode_attention) and each
    # sequence's rope/learned position continues from its OWN last real
    # token, not the bucket boundary — decoded tokens are then
    # bit-identical to an unpadded run (K/V just live at shifted slots).
    valid_len = cache.get("valid_len")
    prefix_len = cache.get("prefill_len")
    if valid_len is not None:
        positions = (valid_len[:, None] + (pos - prefix_len)).astype(jnp.int32)
    else:
        positions = jnp.full((b, 1), pos, jnp.int32)
    if cfg.pos_embed == "learned":
        x = x + jnp.take(params["pos_table"], positions[:, 0] % params["pos_table"].shape[0], axis=0)[:, None]
    moe = cfg.family == "moe"
    has_cross = cfg.family == "encdec"
    layers = params["layers"]

    def body(carry, scanned):
        x, k_all, v_all = carry
        li = scanned["li"]
        layer_p = _decode_layer(layers, li, moe)
        kv = {
            "k": jax.lax.dynamic_index_in_dim(k_all, li, 0, keepdims=False),
            "v": jax.lax.dynamic_index_in_dim(v_all, li, 0, keepdims=False),
        }
        cross_kv = (
            {"k": scanned["cross_k"], "v": scanned["cross_v"]} if has_cross else None
        )
        x2, new_kv, _ = block_apply(
            cfg, layer_p, x, positions, moe=moe, kv_cache=kv, cache_len=pos,
            cross_kv=cross_kv, enc_out=None,
            valid_len=valid_len, prefix_len=prefix_len,
        )
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, new_kv["k"], li, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, new_kv["v"], li, 0)
        return (x2, k_all, v_all), None

    scanned = {"li": jnp.arange(cfg.n_layers, dtype=jnp.int32)}
    if has_cross:
        scanned["cross_k"], scanned["cross_v"] = cache["cross_k"], cache["cross_v"]
    (x, new_k, new_v), _ = cm.scan_or_unroll(
        cfg.scan_layers, body, (x, cache["k"], cache["v"]), scanned,
    )
    logits = lm_logits(cfg, params, x)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = new_k, new_v
    new_cache["len"] = cache["len"] + 1
    return logits, new_cache
