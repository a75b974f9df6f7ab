"""What the benchmark takes from the program under test: its
configuration registry, its model, its serving engine, its tuner and
its kernel dispatch.  Everything that imports ``repro`` goes through
here; the reference (``reference.py``) never does."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import counts, spec

__all__ = ["fields", "arch_config", "stated_model", "prefill_census", "decode_census"]

#: stated key -> ArchConfig field, for every family; a family's file in
#: ``families/`` adds its own (``FIELDS``)
_FIELDS = {
    "family": "family", "n_layers": "n_layers", "d_model": "d_model",
    "n_heads": "n_heads", "n_kv_heads": "n_kv_heads", "head_dim": "head_dim",
    "d_ff": "d_ff", "vocab_size": "vocab_size", "mlp": "mlp_kind",
    "norm": "norm", "norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "tie_embeddings": "tie_embeddings", "qkv_bias": "qkv_bias",
    "dtype": "param_dtype",
}
#: stated only where a family departs from them: rotary positions and no
#: softcap, which the program must run where the file does not state them
_DEFAULTS = {"pos_embed": "rope", "attn_softcap": 0.0}


def fields(family: str) -> dict:
    """Stated key -> ArchConfig field that every file of ``family`` states."""
    return _FIELDS | spec.load_family(family).FIELDS


def arch_config(config: dict, rehearsal: bool = False):
    """The program's ArchConfig for a configuration file, checked
    against the sizes the file states: every stated key against its
    field, the shared ones and the family's.  A stated key that no field
    checks is refused.  A rehearsal on the CPU runs the registry's tiny
    ``reduced()`` variant of it, in bf16."""
    from repro.configs.registry import get_arch

    cfg = dataclasses.replace(get_arch(config["arch"]), **config["overrides"])
    if rehearsal:
        return cfg.reduced(param_dtype="bfloat16", compute_dtype="bfloat16")
    model = config["model"]
    want = fields(model["family"])
    unchecked = sorted(set(model) - set(want) - set(_DEFAULTS))
    missing = sorted(set(want) - set(model))
    if unchecked or missing:
        raise ValueError(
            f"{config['arch']}: stated keys with no ArchConfig field {unchecked} (a family "
            f"maps its own in families/{model['family']}.py FIELDS), keys not stated {missing}")
    stated = {k: (model[k], f) for k, f in want.items()}
    stated |= {k: (model.get(k, v), k) for k, v in _DEFAULTS.items()}
    stated["compute_dtype"] = (model["dtype"], "compute_dtype")
    bad = {k: (v, getattr(cfg, f)) for k, (v, f) in stated.items() if v != getattr(cfg, f)}
    if bad:
        raise ValueError(f"program config {cfg.name} differs from the stated sizes: {bad}")
    return cfg


def stated_model(config: dict, cfg, rehearsal: bool = False) -> dict:
    """The sizes the reference and the counts use: the file's, or the
    rehearsal config's own under the same keys."""
    if not rehearsal:
        return dict(config["model"])
    return {k: getattr(cfg, f) for k, f in fields(cfg.family).items()} | {
        "head_dim": cfg.resolved_head_dim}


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
