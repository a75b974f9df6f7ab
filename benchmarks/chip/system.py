"""What the benchmark takes from the program under test: its
configuration registry, its model, its serving engine, its tuner and
its kernel dispatch.  Everything that imports ``repro`` goes through
here; the reference (``reference.py``) never does."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import counts

__all__ = ["arch_config", "stated_model", "prefill_census", "decode_census"]

#: stated key -> ArchConfig field
_FIELDS = {
    "family": "family", "n_layers": "n_layers", "d_model": "d_model",
    "n_heads": "n_heads", "n_kv_heads": "n_kv_heads", "head_dim": "head_dim",
    "d_ff": "d_ff", "vocab_size": "vocab_size", "mlp": "mlp_kind",
    "norm": "norm", "norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "tie_embeddings": "tie_embeddings", "qkv_bias": "qkv_bias",
    "dtype": "param_dtype",
}


def arch_config(config: dict, rehearsal: bool = False):
    """The program's ArchConfig for a configuration file, checked
    against the sizes the file states.  A rehearsal on the CPU runs the
    registry's tiny ``reduced()`` variant of it, in bf16."""
    from repro.configs.registry import get_arch

    cfg = dataclasses.replace(get_arch(config["arch"]), **config["overrides"])
    if rehearsal:
        return cfg.reduced(param_dtype="bfloat16", compute_dtype="bfloat16")
    bad = {
        k: (v, getattr(cfg, f)) for k, f in _FIELDS.items()
        if (v := config["model"][k]) != getattr(cfg, f)
    }
    if cfg.compute_dtype != config["model"]["dtype"]:
        bad["compute_dtype"] = (config["model"]["dtype"], cfg.compute_dtype)
    if cfg.pos_embed != "rope" or cfg.attn_softcap:
        bad["positions"] = ("rope, no softcap", (cfg.pos_embed, cfg.attn_softcap))
    if bad:
        raise ValueError(f"program config {cfg.name} differs from the stated sizes: {bad}")
    return cfg


def stated_model(config: dict, cfg, rehearsal: bool = False) -> dict:
    """The sizes the reference and the counts use: the file's, or the
    rehearsal config's own."""
    if not rehearsal:
        return dict(config["model"])
    return {k: getattr(cfg, f) for k, f in _FIELDS.items()} | {
        "mlp": cfg.mlp_kind, "dtype": cfg.param_dtype,
        "head_dim": cfg.resolved_head_dim,
    }


def abstract_params(cfg):
    from repro.models.api import Model

    return Model(cfg).abstract_params()


def prefill_census(cfg, batch: int, prompt_len: int, max_len: int) -> list:
    """Kernel launches of one bucketed prefill call, as the engine
    traces it (``Model.prefill`` with per-row last positions)."""
    from repro.models.api import Model

    model = Model(cfg)
    fn = lambda p, b, last: model.prefill(p, b, max_len, last_idx=last)  # noqa: E731
    jaxpr = jax.make_jaxpr(fn)(
        abstract_params(cfg),
        {"tokens": jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)},
        jax.ShapeDtypeStruct((batch,), jnp.int32),
    )
    return counts.census(jaxpr)


def decode_census(cfg, batch: int, max_len: int, steps: int) -> list:
    """Kernel launches of ``steps`` decode steps at ``batch``."""
    from repro.models.api import Model

    model = Model(cfg)
    cache = model.abstract_cache(batch, max_len)
    cache["valid_len"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    cache["prefill_len"] = jax.ShapeDtypeStruct((), jnp.int32)
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    one = counts.census(jax.make_jaxpr(model.decode_step)(abstract_params(cfg), cache, tok))
    return [dataclasses.replace(x, count=x.count * steps) for x in one]
