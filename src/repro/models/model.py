"""Top-level model: embeddings -> stack -> final norm -> (tied) unembed.

One ``Model`` class serves all 10 assigned architectures; family-specific
behaviour is driven entirely by ``ModelConfig``. Modality frontends for
[audio]/[vlm] archs are stubs per the assignment: training batches carry
precomputed frame/patch *embeddings* of shape (B, S, d_model) instead of
token ids (the transformer backbone is what we implement).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import params as pdefs
from repro.models import stack as stack_mod
from repro.models.layers import (embed_defs, embed_lookup, rms_norm,
                                 sharded_xent, softcap, unembed_logits)
from repro.sharding.rules import ParallelContext, attn_dims, pad_to


class Model:
    def __init__(self, cfg: ModelConfig, tp: int = 1):
        self.cfg = cfg
        self.tp = tp
        self.vocab_padded = pad_to(cfg.vocab_size, tp)
        self.dims = attn_dims(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, tp)

    # ------------------------------------------------------------------
    # Parameter definitions
    # ------------------------------------------------------------------
    def defs(self):
        cfg = self.cfg
        d = {
            "embed": embed_defs(self.vocab_padded, cfg.d_model),
            "stack": stack_mod.stack_defs(cfg, self.tp),
            "final_norm": pdefs.norm_scale(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            # unit-RMS hidden states @ d^-1/2-scaled rows: unit-variance
            # logits, so the initial loss sits near ln(vocab)
            d["unembed"] = embed_defs(self.vocab_padded, cfg.d_model,
                                      scale=cfg.d_model ** -0.5)
        if cfg.mtp is not None:
            desc = stack_mod.LayerDesc("attn", 0)
            d["mtp"] = {
                "proj": pdefs.linear(2 * cfg.d_model, cfg.d_model),
                "block": stack_mod.layer_defs(cfg, desc, self.dims, self.tp),
                "norm": pdefs.norm_scale(cfg.d_model),
            }
        return d

    def param_specs(self):
        return pdefs.param_specs(self.defs())

    def abstract_params(self, mesh=None):
        return pdefs.abstract_params(self.defs(), mesh)

    def init(self, rng):
        return pdefs.init_params(self.defs(), rng)

    # ------------------------------------------------------------------
    # Input specs (dry-run stand-ins and real-batch shapes)
    # ------------------------------------------------------------------
    def train_batch_defs(self, batch: int, seq: int):
        cfg = self.cfg
        if cfg.frontend is not None:
            return {
                "embeddings": pdefs.ParamDef((batch, seq, cfg.d_model),
                                             P("data", None, None), dtype=cfg.dtype),
                "labels": pdefs.ParamDef((batch, seq), P("data", None),
                                         dtype="int32"),
            }
        return {
            "tokens": pdefs.ParamDef((batch, seq), P("data", None), dtype="int32"),
            "labels": pdefs.ParamDef((batch, seq), P("data", None), dtype="int32"),
        }

    # ------------------------------------------------------------------
    # Forward / loss
    # ------------------------------------------------------------------
    def _embed_in(self, params, batch, ctx: ParallelContext):
        if "embeddings" in batch:
            return batch["embeddings"].astype(jnp.dtype(self.cfg.dtype))
        return embed_lookup(params["embed"], batch["tokens"], ctx, self.cfg.dtype)

    def _unembed(self, params, h, ctx: ParallelContext):
        table = params.get("unembed", params["embed"])
        logits = unembed_logits(table, ctx.tp_copy(h), self.cfg.dtype)
        return softcap(logits.astype(jnp.float32), self.cfg.logit_softcap)

    def loss(self, params, batch, ctx: ParallelContext, *,
             remat_policy: str = "full", chunk: int = 2048):
        """Next-token (or masked-target) CE. Returns (loss, metrics)."""
        cfg = self.cfg
        x = self._embed_in(params, batch, ctx)
        h, aux = stack_mod.stack_train(params["stack"], x, cfg, ctx,
                                       remat_policy=remat_policy, chunk=chunk)
        h = rms_norm(params["final_norm"], h, cfg.norm_eps)
        logits = self._unembed(params, h, ctx)
        labels = batch["labels"]
        ce = sharded_xent(logits, labels, ctx, true_vocab=cfg.vocab_size)
        loss = ce + aux
        metrics = {"ce": ce, "aux": aux}
        if cfg.mtp is not None and "tokens" in batch:
            mtp_ce = self._mtp_loss(params, h, batch, ctx)
            loss = loss + cfg.mtp.loss_weight * mtp_ce
            metrics["mtp_ce"] = mtp_ce
        return loss, metrics

    def _mtp_loss(self, params, h, batch, ctx: ParallelContext):
        """DeepSeek-V3 multi-token prediction: predict t+2 from [h_t; emb_{t+1}]."""
        cfg = self.cfg
        mp = params["mtp"]
        emb_next = embed_lookup(params["embed"],
                                jnp.roll(batch["tokens"], -1, axis=1), ctx,
                                cfg.dtype)
        z = jnp.concatenate([h, emb_next], axis=-1) @ mp["proj"].astype(h.dtype)
        desc = stack_mod.LayerDesc("attn", 0)
        z, _ = stack_mod.layer_train(mp["block"], z, cfg, desc, self.dims, ctx)
        z = rms_norm(mp["norm"], z, cfg.norm_eps)
        logits = self._unembed(params, z, ctx)
        labels2 = jnp.roll(batch["labels"], -1, axis=1)
        return sharded_xent(logits, labels2, ctx, true_vocab=cfg.vocab_size)

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------
    def cache_defs(self, batch: int, max_len: int, *, seq_sharded: bool = False):
        return stack_mod.stack_cache_defs(self.cfg, self.tp, batch, max_len,
                                          seq_sharded=seq_sharded)

    def init_cache(self, batch: int, max_len: int, *, seq_sharded: bool = False):
        return stack_mod.init_cache_value(
            self.cache_defs(batch, max_len, seq_sharded=seq_sharded))

    def prefill(self, params, tokens, ctx: ParallelContext, *, max_len: int,
                chunk: int = 2048):
        """tokens (B,S) -> (last-position logits (B, V/tp), caches)."""
        if self.cfg.is_encoder:
            raise ValueError(f"{self.cfg.name} is encoder-only: no decode path")
        x = embed_lookup(params["embed"], tokens, ctx, self.cfg.dtype)
        h, caches = stack_mod.stack_prefill(params["stack"], x, self.cfg, ctx,
                                            max_len=max_len, chunk=chunk)
        h = rms_norm(params["final_norm"], h[:, -1:], self.cfg.norm_eps)
        return self._unembed(params, h, ctx)[:, 0], caches

    def decode_step(self, params, token, caches, pos, ctx: ParallelContext, *,
                    max_len: int):
        """token (B,1) int32, pos scalar -> (logits (B, V/tp), new caches)."""
        if self.cfg.is_encoder:
            raise ValueError(f"{self.cfg.name} is encoder-only: no decode path")
        x = embed_lookup(params["embed"], token, ctx, self.cfg.dtype)
        h, caches = stack_mod.stack_decode(params["stack"], x, caches, pos,
                                           self.cfg, ctx, max_len)
        h = rms_norm(params["final_norm"], h, self.cfg.norm_eps)
        return self._unembed(params, h, ctx)[:, 0], caches

    def encode(self, params, batch, ctx: ParallelContext, *, chunk: int = 2048):
        """Encoder-only forward (hubert prefill_32k): returns frame logits."""
        x = self._embed_in(params, batch, ctx)
        h, _ = stack_mod.stack_train(params["stack"], x, self.cfg, ctx,
                                     remat_policy="none", chunk=chunk)
        h = rms_norm(params["final_norm"], h, self.cfg.norm_eps)
        return self._unembed(params, h, ctx)


def greedy_sample(logits_local, ctx: ParallelContext):
    """Argmax over a vocab-sharded logits row. logits_local: (B, V/tp)."""
    vloc = logits_local.shape[-1]
    lo = ctx.model_index() * vloc
    lmax = jnp.max(logits_local, axis=-1)
    larg = jnp.argmax(logits_local, axis=-1).astype(jnp.int32) + lo
    gmax = ctx.pmax_model(lmax)
    cand = jnp.where(lmax >= gmax, larg, jnp.int32(2**30))
    return -ctx.pmax_model(-cand)
