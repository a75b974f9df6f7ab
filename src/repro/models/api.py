"""Unified model API — one dispatch surface over all families.

``Model(cfg)`` gives init/loss/prefill/decode for any assigned arch;
``batch_specs`` produces the ShapeDtypeStruct stand-ins the dry-run
lowers against (the modality frontends are stubs per the assignment:
``frontend_embeds`` / ``enc_frames`` arrive as precomputed embeddings).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models import hybrid as hy
from repro.models import mamba2 as mb
from repro.models import transformer as tf

__all__ = ["Model"]


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    # -- params ---------------------------------------------------------------
    def init_params(self, key) -> dict:
        """Random parameters from ``key``, built as one compiled program:
        each weight is drawn in f32 and cast to the param dtype inside
        its fusion, so no f32 copy of a stacked weight is ever held
        (eagerly, yi-6b's last (32, 11008, 4096) MLP weight alone needs
        a 5.8 GB f32 temporary next to the other 12 GB)."""
        return _init_params(self.cfg, key)

    def abstract_params(self) -> dict:
        return jax.eval_shape(self.init_params, jax.random.PRNGKey(0))

    # -- training -------------------------------------------------------------
    def logits(self, params, batch):
        c = self.cfg
        if c.family in ("dense", "vlm", "moe", "encdec"):
            return tf.forward_logits(c, params, batch)
        if c.family == "ssm":
            return mb.mamba_lm_forward(c, params, batch)
        if c.family == "hybrid":
            return hy.hybrid_forward(c, params, batch)
        raise ValueError(c.family)

    def hidden(self, params, batch):
        c = self.cfg
        if c.family in ("dense", "vlm", "moe", "encdec"):
            return tf.forward_hidden(c, params, batch)
        if c.family == "ssm":
            return mb.mamba_lm_hidden(c, params, batch)
        if c.family == "hybrid":
            return hy.hybrid_hidden(c, params, batch)
        raise ValueError(c.family)

    def loss(self, params, batch):
        """Streaming (sequence-chunked) CE — never materializes the full
        (B, S, V) logits tensor (see transformer.streaming_lm_loss)."""
        x, aux = self.hidden(params, batch)
        labels = batch["labels"]
        if x.shape[1] != labels.shape[1]:  # vlm frontend positions unsupervised
            pad = x.shape[1] - labels.shape[1]
            labels = jnp.concatenate(
                [jnp.full((labels.shape[0], pad), -1, labels.dtype), labels],
                axis=1,
            )
        return tf.streaming_lm_loss(self.cfg, params, x, labels, aux)

    # -- serving ----------------------------------------------------------------
    def prefill(self, params, batch, max_len: int, last_idx=None):
        """``last_idx`` (B,) selects each sequence's last real position
        for the seed logits (bucket-padded serving); attention families
        only — SSM/hybrid state would be polluted by pad tokens, so the
        engine never pads those."""
        c = self.cfg
        if c.family in ("dense", "vlm", "moe", "encdec"):
            return tf.prefill(c, params, batch, max_len, last_idx=last_idx)
        if last_idx is not None:
            raise ValueError(f"family {c.family} does not support padded prefill")
        if c.family == "ssm":
            return mb.mamba_lm_prefill(c, params, batch, max_len)
        if c.family == "hybrid":
            return hy.hybrid_prefill(c, params, batch, max_len)
        raise ValueError(c.family)

    def init_cache(self, batch_size: int, max_len: int):
        c = self.cfg
        if c.family in ("dense", "vlm", "moe", "encdec"):
            return tf.init_cache(c, batch_size, max_len)
        if c.family == "ssm":
            return mb.mamba_lm_init_cache(c, batch_size, max_len)
        if c.family == "hybrid":
            return hy.hybrid_init_cache(c, batch_size, max_len)
        raise ValueError(c.family)

    def abstract_cache(self, batch_size: int, max_len: int):
        return jax.eval_shape(lambda: self.init_cache(batch_size, max_len))

    def decode_step(self, params, cache, tokens):
        c = self.cfg
        if c.family in ("dense", "vlm", "moe", "encdec"):
            return tf.decode_step(c, params, cache, tokens)
        if c.family == "ssm":
            return mb.mamba_lm_decode_step(c, params, cache, tokens)
        if c.family == "hybrid":
            return hy.hybrid_decode_step(c, params, cache, tokens)
        raise ValueError(c.family)

    # -- dry-run input specs --------------------------------------------------
    def batch_specs(self, shape: ShapeSpec) -> dict:
        """ShapeDtypeStruct stand-ins for one step's data inputs."""
        c = self.cfg
        b, s = shape.global_batch, shape.seq_len
        tok = jnp.int32
        emb_dt = jnp.dtype(c.compute_dtype)
        specs: dict = {}
        if shape.kind in ("train", "prefill"):
            n_front = c.n_frontend_tokens if c.frontend != "none" else 0
            s_text = s - n_front
            specs["tokens"] = jax.ShapeDtypeStruct((b, s_text), tok)
            if shape.kind == "train":
                specs["labels"] = jax.ShapeDtypeStruct((b, s_text), tok)
            if n_front:
                specs["frontend_embeds"] = jax.ShapeDtypeStruct(
                    (b, n_front, c.d_model), emb_dt
                )
            if c.family == "encdec":
                specs["enc_frames"] = jax.ShapeDtypeStruct(
                    (b, c.encoder_len, c.d_model), emb_dt
                )
        else:  # decode: one new token against a seq_len-deep cache
            specs["tokens"] = jax.ShapeDtypeStruct((b, 1), tok)
        return specs

    def supports_shape(self, shape: ShapeSpec) -> tuple[bool, str]:
        c = self.cfg
        if shape.name == "long_500k" and c.family not in ("ssm", "hybrid"):
            return False, "full quadratic attention: 512k KV cache skipped per assignment"
        return True, ""


@functools.partial(jax.jit, static_argnums=0)
def _init_params(c: ArchConfig, key) -> dict:
    if c.family in ("dense", "vlm", "moe", "encdec"):
        return tf.init_params(c, key)
    if c.family == "ssm":
        return mb.init_mamba_lm(c, key)
    if c.family == "hybrid":
        return hy.init_hybrid_params(c, key)
    raise ValueError(f"unknown family {c.family}")
