"""RecurrentGemma / Griffin: RG-LRU recurrent blocks + local-attention blocks.

Layer pattern (arXiv 2402.19427): repeating (recurrent, recurrent, local-attn)
— we scan over stacked superblocks of 3 plus a stacked tail of leftover
recurrent layers (26 = 3*8 + 2).

RG-LRU (Real-Gated Linear Recurrent Unit):
    r_t = sigmoid(W_a x_t + b_a)            recurrence gate
    i_t = sigmoid(W_x x_t + b_x)            input gate
    a_t = exp(-c * softplus(Lambda) * r_t)  diagonal decay, c = 8
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

The diagonal linear recurrence is associative -> ``jax.lax.associative_scan``
(log-depth, parallelizes over time; this is the TPU-native answer to the
GPU kernel in the paper).  The Pallas kernel in ``repro.kernels.rglru`` is
the fused single-pass variant for the memory-bound regime.

Recurrent state for decode is O(1): h (B, d_rnn) + a (conv_width-1)-token
convolution buffer -> long_500k runs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import attention, layers
from .common import ModelConfig, Spec, init_params, param_axes, param_shapes, rms_norm

RGLRU_C = 8.0


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------
def rglru_spec(cfg: ModelConfig, stacked: int = 0) -> dict:
    d, dr, cw = cfg.d_model, cfg.d_rnn_, cfg.conv_width
    lead = (stacked,) if stacked else ()
    lx = ("layers",) if stacked else ()
    return {
        # two input branches
        "w_gate": Spec(lead + (d, dr), lx + ("embed", "rnn")),     # gelu branch
        "w_rec_in": Spec(lead + (d, dr), lx + ("embed", "rnn")),   # conv branch
        # temporal depthwise conv
        "conv_w": Spec(lead + (cw, dr), lx + ("conv", "rnn"), scale=0.5),
        "conv_b": Spec(lead + (dr,), lx + ("rnn",), init="zeros"),
        # RG-LRU gates (dense, simplification of Griffin's block-diagonal)
        "w_a": Spec(lead + (dr, dr), lx + ("rnn", None)),
        "b_a": Spec(lead + (dr,), lx + ("rnn",), init="zeros"),
        "w_x": Spec(lead + (dr, dr), lx + ("rnn", None)),
        "b_x": Spec(lead + (dr,), lx + ("rnn",), init="zeros"),
        "lam": Spec(lead + (dr,), lx + ("rnn",), init="rglru_a"),
        # output projection
        "w_out": Spec(lead + (dr, d), lx + ("rnn", "embed")),
    }


def rec_layer_spec(cfg: ModelConfig, stacked: int = 0) -> dict:
    return {
        "norm1": layers.norm_spec(cfg, stacked=stacked),
        "rec": rglru_spec(cfg, stacked=stacked),
        "norm2": layers.norm_spec(cfg, stacked=stacked),
        "mlp": layers.mlp_spec(cfg, stacked=stacked),
    }


def attn_layer_spec(cfg: ModelConfig, stacked: int = 0) -> dict:
    return {
        "norm1": layers.norm_spec(cfg, stacked=stacked),
        "attn": attention.attn_spec(cfg, stacked=stacked),
        "norm2": layers.norm_spec(cfg, stacked=stacked),
        "mlp": layers.mlp_spec(cfg, stacked=stacked),
    }


# ---------------------------------------------------------------------------
# RG-LRU core
# ---------------------------------------------------------------------------
def rglru_scan(x, log_a):
    """h_t = exp(log_a_t) * h_{t-1} + x_t  via associative scan over axis 1.

    x, log_a: (B, S, Dr).  Returns h: (B, S, Dr) in fp32.
    """
    def combine(c1, c2):
        la1, x1 = c1
        la2, x2 = c2
        return la1 + la2, jnp.exp(la2) * x1 + x2

    la, h = jax.lax.associative_scan(combine, (log_a, x), axis=1)
    return h


def rglru_apply(p, x, cfg: ModelConfig, shd, state: Optional[dict] = None):
    """x: (B,S,Dr) conv output -> (h (B,S,Dr), new recurrent state h_last)."""
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(jnp.einsum("bsd,dk->bsk", xf, p["w_a"].astype(jnp.float32))
                       + p["b_a"].astype(jnp.float32))
    i = jax.nn.sigmoid(jnp.einsum("bsd,dk->bsk", xf, p["w_x"].astype(jnp.float32))
                       + p["b_x"].astype(jnp.float32))
    log_a = -RGLRU_C * jax.nn.softplus(p["lam"].astype(jnp.float32)) * r
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * xf)
    gated = shd.constraint(gated, ("batch", "seq", "rnn"))
    if state is not None and "h" in state:
        # fold carried state into the first step: x_0 += a_0 * h_prev
        gated = gated.at[:, 0].add(jnp.exp(log_a[:, 0]) * state["h"])
    h = rglru_scan(gated, log_a)
    return h, h[:, -1]


def temporal_conv(p, x, cfg: ModelConfig, prev: Optional[jax.Array] = None):
    """Causal depthwise conv width cw.  prev: (B, cw-1, Dr) decode buffer."""
    cw = cfg.conv_width
    w = p["conv_w"].astype(x.dtype)                     # (cw, Dr)
    if prev is None:
        pad = jnp.zeros((x.shape[0], cw - 1, x.shape[2]), x.dtype)
    else:
        pad = prev.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)              # (B, S+cw-1, Dr)
    out = sum(xp[:, j:j + x.shape[1]] * w[j] for j in range(cw))
    new_buf = xp[:, -(cw - 1):] if cw > 1 else None
    return out + p["conv_b"].astype(x.dtype), new_buf


def recurrent_block(p, x, cfg: ModelConfig, shd, state: Optional[dict] = None):
    """Griffin recurrent block.  x: (B,S,D) -> (out, new_state)."""
    dt = x.dtype
    gate = jax.nn.gelu(jnp.einsum("bsd,dk->bsk", x, p["w_gate"].astype(dt)))
    rec = jnp.einsum("bsd,dk->bsk", x, p["w_rec_in"].astype(dt))
    rec = shd.constraint(rec, ("batch", "seq", "rnn"))
    rec, conv_buf = temporal_conv(p, rec, cfg,
                                  None if state is None else state.get("conv"))
    h, h_last = rglru_apply(p, rec, cfg, shd, state)
    out = (gate.astype(jnp.float32) * h).astype(dt)
    out = jnp.einsum("bsk,kd->bsd", out, p["w_out"].astype(dt))
    new_state = None
    if state is not None:
        new_state = {"h": h_last,
                     "conv": conv_buf.astype(state["conv"].dtype)
                     if conv_buf is not None else state["conv"]}
    return out, new_state


def init_rec_state(cfg: ModelConfig, batch: int):
    return {
        "h": jnp.zeros((batch, cfg.d_rnn_), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, cfg.d_rnn_),
                          jnp.float32),
    }


def rec_state_axes():
    return {"h": ("batch", "rnn"), "conv": ("batch", None, "rnn")}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
class GriffinLM:
    """RecurrentGemma-style hybrid LM: (rec, rec, local-attn) superblocks."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        assert cfg.attn_window > 0, "hybrid arch needs a local window"
        self.n_super = cfg.n_layers // 3
        self.n_tail = cfg.n_layers - 3 * self.n_super   # trailing rec layers

    # ------------------------------------------------------------------
    def specs(self):
        cfg, ns, nt = self.cfg, self.n_super, self.n_tail
        out = {
            "embed": layers.embed_spec(cfg),
            "super": {
                "rec1": rec_layer_spec(cfg, stacked=ns),
                "rec2": rec_layer_spec(cfg, stacked=ns),
                "attn": attn_layer_spec(cfg, stacked=ns),
            },
            "final_norm": layers.norm_spec(cfg),
            "head": layers.head_spec(cfg),
        }
        if nt:
            out["tail"] = rec_layer_spec(cfg, stacked=nt)
        return out

    def init(self, rng):
        return init_params(self.specs(), rng, self.cfg.param_dtype)

    def shapes(self):
        return param_shapes(self.specs(), self.cfg.param_dtype)

    def axes(self):
        return param_axes(self.specs())

    # ------------------------------------------------------------------
    def _rec_layer(self, p, x, shd, state=None):
        cfg = self.cfg
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        out, new_state = recurrent_block(p["rec"], h, cfg, shd, state)
        x = x + out
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        x = x + layers.mlp(p["mlp"], h, cfg, shd)
        return shd.constraint(x, ("batch", "seq", None)), new_state

    def _attn_layer(self, p, x, shd, cache=None):
        cfg = self.cfg
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        out, new_cache = attention.attention_block(p["attn"], h, cfg, shd,
                                                   cache=cache)
        x = x + out
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        x = x + layers.mlp(p["mlp"], h, cfg, shd)
        return shd.constraint(x, ("batch", "seq", None)), new_cache

    def _super_fwd(self, x, sp, shd):
        x, _ = self._rec_layer(sp["rec1"], x, shd)
        x, _ = self._rec_layer(sp["rec2"], x, shd)
        x, _ = self._attn_layer(sp["attn"], x, shd)
        return x

    def _trunk(self, params, x, shd, remat: Optional[str] = None):
        def body(carry, sp):
            f = jax.checkpoint(
                lambda c, s_: self._super_fwd(c, s_, shd),
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            return f(carry, sp), None

        x, _ = jax.lax.scan(body, x, params["super"])
        if self.n_tail:
            def tail_body(carry, tp):
                y, _ = self._rec_layer(tp, carry, shd)
                return y, None
            x, _ = jax.lax.scan(tail_body, x, params["tail"])
        return x

    def loss_fn(self, params, batch, shd, remat: Optional[str] = None):
        cfg = self.cfg
        x = layers.embed(params["embed"], batch["tokens"], cfg, shd)
        x = self._trunk(params, x, shd, remat)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        loss = layers.chunked_lm_loss(params.get("head"), params["embed"], x,
                                      batch["labels"], cfg, shd)
        return loss, {"xent": loss, "aux": jnp.zeros((), jnp.float32)}

    # ------------------------------------------------------------------
    # serving: stacked per-group states
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype: str = "bfloat16"):
        cfg, ns, nt = self.cfg, self.n_super, self.n_tail
        rec = init_rec_state(cfg, batch)
        kv = attention.init_kv_cache(cfg, batch, max_len, dtype=dtype)

        def stack(tree, n):
            return jax.tree.map(lambda a: jnp.zeros((n,) + a.shape, a.dtype),
                                tree)

        return {
            "rec1": stack(rec, ns), "rec2": stack(rec, ns),
            "attn": {"k": stack(kv["k"], ns), "v": stack(kv["v"], ns)},
            "tail": stack(rec, nt) if nt else {},
            "len": jnp.zeros((), jnp.int32),
        }

    def cache_shapes(self, batch: int, max_len: int, dtype: str = "bfloat16"):
        return jax.eval_shape(
            lambda: self.init_cache(batch, max_len, dtype))

    def cache_axes(self):
        ra = {"h": ("stack", "batch", "rnn"),
              "conv": ("stack", "batch", None, "rnn")}
        return {
            "rec1": ra, "rec2": ra,
            "attn": {"k": ("stack", "batch", "kv_seq", "kv_heads"),
                     "v": ("stack", "batch", "kv_seq", "kv_heads")},
            "tail": ra if self.n_tail else {},
            "len": (),
        }

    def decode_step(self, params, cache, batch, shd):
        cfg = self.cfg
        x = layers.embed(params["embed"], batch["tokens"], cfg, shd)

        def scan_body(carry, xs):
            x, kv = carry
            sp, st, layer = xs
            x, s1 = self._rec_layer(sp["rec1"], x, shd, state=st["rec1"])
            x, s2 = self._rec_layer(sp["rec2"], x, shd, state=st["rec2"])
            x, kv = self._attn_layer(sp["attn"], x, shd, cache={
                **kv, "len": cache["len"], "layer": layer})
            return (x, kv), {"rec1": s1, "rec2": s2}

        sts = {"rec1": cache["rec1"], "rec2": cache["rec2"]}
        (x, kv), new_sts = jax.lax.scan(
            scan_body, (x, cache["attn"]),
            (params["super"], sts, jnp.arange(self.n_super)))
        new_cache = {
            "rec1": new_sts["rec1"], "rec2": new_sts["rec2"],
            "attn": kv,
            "tail": cache.get("tail", {}),
            "len": cache["len"] + 1,
        }
        if self.n_tail:
            def tail_body(carry, xs):
                tp, st = xs
                y, ns = self._rec_layer(tp, carry, shd, state=st)
                return y, ns
            x, new_tail = jax.lax.scan(tail_body, x,
                                       (params["tail"], cache["tail"]))
            new_cache["tail"] = new_tail
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = layers.lm_logits(params.get("head"), params["embed"], x,
                                  cfg, shd)
        return logits, new_cache

    def prefill(self, params, batch, shd, max_len: Optional[int] = None):
        """Sequence prefill producing decode states (rec h + ring kv)."""
        cfg = self.cfg
        x = layers.embed(params["embed"], batch["tokens"], cfg, shd)
        b, s = batch["tokens"].shape
        max_len = max_len or s

        def super_fwd(x, sp):
            st = init_rec_state(cfg, b)
            kv0 = attention.init_kv_cache(cfg, b, max_len, dtype="bfloat16")
            x, s1 = self._rec_layer(sp["rec1"], x, shd,
                                    state={**st})
            x, s2 = self._rec_layer(sp["rec2"], x, shd, state={**st})
            x, kv = self._attn_layer(sp["attn"], x, shd, cache=kv0)
            return x, {"rec1": s1, "rec2": s2,
                       "attn_k": kv["k"], "attn_v": kv["v"]}

        def body(carry, sp):
            x, new = jax.checkpoint(
                super_fwd,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            )(carry, sp)
            return x, new

        x, sts = jax.lax.scan(body, x, params["super"])
        cache = {"rec1": sts["rec1"], "rec2": sts["rec2"],
                 "attn": {"k": sts["attn_k"], "v": sts["attn_v"]},
                 "tail": {}, "len": jnp.full((), s, jnp.int32)}
        if self.n_tail:
            def tail_body(carry, tp):
                st = init_rec_state(cfg, b)
                y, ns = self._rec_layer(tp, carry, shd, state=st)
                return y, ns
            x, new_tail = jax.lax.scan(tail_body, x, params["tail"])
            cache["tail"] = new_tail
        x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
        logits = layers.lm_logits(params.get("head"), params["embed"], x,
                                  cfg, shd)
        return logits[:, 0], cache
