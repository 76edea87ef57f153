"""Decoder-only transformer LM (dense + MoE), scan-over-layers.

Covers 8 of the 10 assigned architectures (dense, moe, vlm- and
audio-backbones).  Layers are stacked along a leading ``L`` dim and applied
with ``jax.lax.scan`` + per-layer ``jax.checkpoint`` — this keeps the HLO
O(1) in depth (compile time) and caps activation memory at one layer
(remat), both prerequisites for 314B-parameter dry-runs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import attention, layers, moe as moe_lib
from .common import ModelConfig, Spec, init_params, param_axes, param_shapes, rms_norm

REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


class TransformerLM:
    """Pure-pytree decoder-only LM; all state explicit."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------
    # parameter declaration
    # ------------------------------------------------------------------
    def specs(self):
        cfg = self.cfg
        L = cfg.n_layers
        layer = {
            "norm1": layers.norm_spec(cfg, stacked=L),
            "attn": attention.attn_spec(cfg, stacked=L),
            "norm2": layers.norm_spec(cfg, stacked=L),
        }
        if cfg.n_experts:
            layer["moe"] = moe_lib.moe_spec(cfg, stacked=L)
        else:
            layer["mlp"] = layers.mlp_spec(cfg, stacked=L)
        return {
            "embed": layers.embed_spec(cfg),
            "layers": layer,
            "final_norm": layers.norm_spec(cfg),
            "head": layers.head_spec(cfg),
        }

    def init(self, rng):
        return init_params(self.specs(), rng, self.cfg.param_dtype)

    def shapes(self):
        return param_shapes(self.specs(), self.cfg.param_dtype)

    def axes(self):
        return param_axes(self.specs())

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def _inputs(self, params, batch, shd):
        cfg = self.cfg
        if cfg.input_mode == "embeddings":
            x = batch["embeds"].astype(jnp.dtype(cfg.compute_dtype))
            x = shd.constraint(x, ("batch", "seq", None))
        else:
            x = layers.embed(params["embed"], batch["tokens"], cfg, shd)
        return x

    def _layer_fn(self, x, aux, lp, shd, cache=None):
        cfg = self.cfg
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        attn_out, new_cache = attention.attention_block(
            lp["attn"], h, cfg, shd, cache=cache)
        x = x + attn_out
        h = rms_norm(x, lp["norm2"], cfg.norm_eps)
        if cfg.n_experts:
            mo, a = moe_lib.moe_block(lp["moe"], h, cfg, shd)
            aux = aux + a
        else:
            mo = layers.mlp(lp["mlp"], h, cfg, shd)
        x = x + mo
        x = shd.constraint(x, ("batch", "seq", None))
        return x, aux, new_cache

    def _stack(self, params, x, shd, remat: Optional[str] = None):
        cfg = self.cfg

        def body(carry, lp):
            x, aux = carry
            x, aux, _ = self._layer_fn(x, aux, lp, shd)
            return (x, aux), None

        policy = REMAT_POLICIES.get(remat or "dots")
        if remat != "none":
            body = jax.checkpoint(body, policy=policy)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["layers"])
        return x, aux

    def loss_fn(self, params, batch, shd, remat: Optional[str] = None):
        cfg = self.cfg
        x = self._inputs(params, batch, shd)
        x, aux = self._stack(params, x, shd, remat)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        loss = layers.chunked_lm_loss(params.get("head"), params["embed"], x,
                                      batch["labels"], cfg, shd)
        return loss + aux, {"xent": loss, "aux": aux}

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype: str = "bfloat16"):
        cfg = self.cfg
        one = attention.init_kv_cache(cfg, batch, max_len, dtype=dtype)
        return {
            "k": jnp.broadcast_to(one["k"][None], (cfg.n_layers,) + one["k"].shape),
            "v": jnp.broadcast_to(one["v"][None], (cfg.n_layers,) + one["v"].shape),
            "len": one["len"],
        }

    def cache_shapes(self, batch: int, max_len: int, dtype: str = "bfloat16"):
        return jax.eval_shape(
            lambda: self.init_cache(batch, max_len, dtype))

    def cache_axes(self):
        return {
            "k": ("layers", "batch", "kv_seq", "kv_heads"),
            "v": ("layers", "batch", "kv_seq", "kv_heads"),
            "len": (),
        }

    def _stack_decode(self, params, x, cache, shd):
        """One-token step through all layers.  The stacked cache rides the
        scan's carry: each layer reads its rows as they stand and writes
        only its new row into the stack (:func:`attention.attention_block`)."""

        def body(carry, xs):
            x, aux, kv = carry
            lp, layer = xs
            x, aux, kv = self._layer_fn(
                x, aux, lp, shd, cache={**kv, "len": cache["len"],
                                        "layer": layer})
            return (x, aux, kv), None

        kv = {"k": cache["k"], "v": cache["v"]}
        (x, _, kv), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32), kv),
            (params["layers"], jnp.arange(self.cfg.n_layers)))
        return x, {**kv, "len": cache["len"] + x.shape[1]}

    def decode_step(self, params, cache, batch, shd):
        """batch: {"tokens": (B,1)} or {"embeds": (B,1,D)} -> (logits, cache)."""
        cfg = self.cfg
        x = self._inputs(params, batch, shd)
        x, new_cache = self._stack_decode(params, x, cache, shd)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = layers.lm_logits(params.get("head"), params["embed"], x,
                                  cfg, shd)
        return logits, new_cache

    def prefill(self, params, batch, shd, max_len: Optional[int] = None):
        """Full-sequence prefill; returns (last-token logits, filled cache)."""
        cfg = self.cfg
        x = self._inputs(params, batch, shd)
        s = x.shape[1]
        max_len = max_len or s

        def body(carry, xs):
            x, aux = carry
            lp = xs
            cache0 = attention.init_kv_cache(cfg, x.shape[0], max_len,
                                             dtype="bfloat16")
            x, aux, new_cache = self._layer_fn(x, aux, lp, shd, cache=cache0)
            return (x, aux), (new_cache["k"], new_cache["v"])

        body = jax.checkpoint(body, policy=REMAT_POLICIES["dots"])
        (x, _), (ks, vs) = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), params["layers"])
        cache = {"k": ks, "v": vs, "len": jnp.full((), s, jnp.int32)}
        x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
        logits = layers.lm_logits(params.get("head"), params["embed"], x,
                                  cfg, shd)
        return logits[:, 0], cache
