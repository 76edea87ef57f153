"""granite-3-2b as the benchmark runs it: the program's model built from
``granite-3-2b.json``, its weights made from the seed, the operations and
bytes its steps need, and a plain float32 reference of its forward pass.

The reference imports nothing of the program.  It makes its weights again
from the seed, layer by layer inside a scan, so at most one layer of
float32 weights is on the device at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import seeding

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# the configuration as the program runs it
# ---------------------------------------------------------------------------
def check_config(c: dict) -> None:
    """The program has no multipliers and no tied head: refuse a file that
    asks for them rather than run something else under its name."""
    dh = c["hidden_size"] // c["num_attention_heads"]
    want = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
            "logits_scaling": 1.0, "attention_multiplier": dh ** -0.5,
            "tie_word_embeddings": False, "attention_bias": False,
            "hidden_act": "silu"}
    for key, value in want.items():
        if c[key] != value:
            raise ValueError(f"{c['name']}: {key}={c[key]!r}, but the "
                             f"program runs {value!r}")


def model_config(c: dict):
    """The program's ``ModelConfig`` for this file."""
    from repro.models.common import ModelConfig
    check_config(c)
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], norm_eps=c["rms_norm_eps"],
        rope_theta=c["rope_theta"], tie_embeddings=c["tie_word_embeddings"],
        param_dtype=c["repo_overrides"]["param_dtype"],
        compute_dtype=c["compute_dtype"])


def build_model(c: dict):
    from repro.models import build_model as build
    return build(model_config(c))


# ---------------------------------------------------------------------------
# weights from the seed, in the program's tree
# ---------------------------------------------------------------------------
def _dims(c):
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    dh = d // h
    return d, h, kv, dh, c["intermediate_size"], c["vocab_size"], c["num_hidden_layers"]


def layer_leaves(c: dict) -> dict:
    """``name -> (shape, std)`` of one layer's weights; std 0 marks a norm
    (all ones)."""
    d, h, kv, dh, f, _, _ = _dims(c)
    return {
        "norm1": ((d,), 0.0),
        "attn/wq": ((d, h * dh), d ** -0.5),
        "attn/wk": ((d, kv * dh), d ** -0.5),
        "attn/wv": ((d, kv * dh), d ** -0.5),
        "attn/wo": ((h * dh, d), (h * dh) ** -0.5),
        "norm2": ((d,), 0.0),
        "mlp/wi": ((d, 2 * f), d ** -0.5),
        "mlp/wo": ((f, d), f ** -0.5),
    }


def _draw(root, name, shape, std, index=None):
    if std == 0.0:
        return jnp.ones(shape, jnp.float32)
    return seeding.normal(root, name, shape, std, index)


def layer_weights(c: dict, root, layer, dtype=jnp.float32) -> dict:
    """One layer's weights as served (bfloat16 values), in ``dtype``."""
    sdt = jnp.dtype(c["torch_dtype"])
    return {name: _draw(root, f"layers/{name}", shape, std, layer)
            .astype(sdt).astype(dtype)
            for name, (shape, std) in layer_leaves(c).items()}


def outer_weights(c: dict, root, dtype=jnp.float32) -> dict:
    d, _, _, _, _, v, _ = _dims(c)
    sdt = jnp.dtype(c["torch_dtype"])
    return {"embed": seeding.normal(root, "embed/tok", (v, d), 1.0)
            .astype(sdt).astype(dtype),
            "final_norm": jnp.ones((d,), dtype),
            "head": seeding.normal(root, "head/w", (d, v), d ** -0.5)
            .astype(sdt).astype(dtype)}


def make_params(c: dict, root, shardings):
    """The program's parameter tree, made on the device from the seed's
    ``root`` key in one jitted call, in the dtype it is served in."""
    n = c["num_hidden_layers"]
    sdt = jnp.dtype(c["torch_dtype"])

    def make(root):
        stacked = {}
        for name, (shape, std) in layer_leaves(c).items():
            if std == 0.0:
                stacked[name] = jnp.ones((n,) + shape, sdt)
            else:
                stacked[name] = seeding.stacked_normal(
                    root, f"layers/{name}", n, shape, std).astype(sdt)
        outer = outer_weights(c, root, sdt)
        return {
            "embed": {"tok": outer["embed"]},
            "layers": {
                "norm1": stacked["norm1"], "norm2": stacked["norm2"],
                "attn": {k: stacked[f"attn/{k}"]
                         for k in ("wq", "wk", "wv", "wo")},
                "mlp": {k: stacked[f"mlp/{k}"] for k in ("wi", "wo")},
            },
            "final_norm": outer["final_norm"],
            "head": {"w": outer["head"]},
        }

    return jax.jit(make, out_shardings=shardings)(root)


# ---------------------------------------------------------------------------
# operations and bytes the algorithm needs (not what a program happens to do)
# ---------------------------------------------------------------------------
def layer_matmul_params(c: dict) -> int:
    d, h, kv, dh, f, _, _ = _dims(c)
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f


def attention_flops(c: dict, batch: int, seq: int) -> float:
    """Causal self-attention over ``seq`` positions, all layers: q.k and
    p.v each take 2 * head_dim FLOPs per (query, key) pair, and a causal
    mask leaves seq * (seq + 1) / 2 pairs per head."""
    _, h, _, dh, _, _, n = _dims(c)
    return 4.0 * n * batch * h * dh * seq * (seq + 1) / 2


def attention_bytes(c: dict, batch: int, seq: int) -> float:
    """Least HBM traffic of that attention: q and the output at every
    query head, k and v at every key-value head, each read or written
    once, in the compute dtype."""
    _, h, kv, dh, _, _, n = _dims(c)
    size = jnp.dtype(c["compute_dtype"]).itemsize
    return float(n * batch * seq * (2 * h * dh + 2 * kv * dh) * size)


def prefill_flops(c: dict, batch: int, seq: int) -> float:
    """Prefill of ``batch`` prompts of ``seq`` tokens: every layer's
    projections at every position, causal attention, and the output head
    at the last position only (prefill returns last-token logits)."""
    d, _, _, _, _, v, n = _dims(c)
    return (2.0 * batch * seq * n * layer_matmul_params(c)
            + attention_flops(c, batch, seq) + 2.0 * batch * d * v)


def decode_flops(c: dict, batch: int, pos: int) -> float:
    """One decode step for the token at position ``pos`` (``pos`` entries
    already cached): projections, the head, and attention over pos + 1
    keys."""
    d, h, _, dh, _, v, n = _dims(c)
    return (2.0 * batch * (n * layer_matmul_params(c) + d * v)
            + 4.0 * n * batch * h * dh * (pos + 1))


def decode_bytes(c: dict, batch: int, pos: int) -> float:
    """Least HBM bytes of that step: every weight it uses read once (the
    embedding only at the batch's rows), the keys and values of the ``pos``
    cached positions read, and the one new entry written."""
    d, _, kv, dh, _, v, n = _dims(c)
    w = jnp.dtype(c["torch_dtype"]).itemsize
    kvb = jnp.dtype(c["cache_dtype"]).itemsize
    weights = (n * (layer_matmul_params(c) + 2 * d) + d * v + d
               + batch * d) * w
    entry = n * batch * 2 * kv * dh * kvb
    return float(weights + entry * pos + entry)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _quantize_fp8(x):
    """Per-tensor scaled float8_e4m3fn rounding (the control's precision)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, precision):
    if precision == "fp8":
        a, b = _quantize_fp8(a), _quantize_fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x: (n, S, H, dh), positions 0..S-1; halves rotated as in GPT-NeoX."""
    s, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def reference_logits(c: dict, root, tokens, start: int,
                     precision: str = "f32", q_chunk: int = 512):
    """float32 logits of the plain forward pass over ``tokens`` (n, S) at
    positions ``start .. S - 1``: (n, S - start, vocab).

    ``precision="f32"`` is the reference (float32, matmuls at HIGHEST);
    ``"fp8"`` rounds both operands of every matmul to per-tensor scaled
    float8_e4m3fn first: the control.
    """
    d, h, kv, dh, f, v, n_layers = _dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    n, s = tokens.shape
    qc = max(i for i in range(1, min(q_chunk, s) + 1) if s % i == 0)

    def attend(q, k, vv):
        # q: (S, H, dh); k, v: (S, KV, dh); causal, chunked over queries
        g = h // kv
        k = jnp.repeat(k, g, axis=1)
        vv = jnp.repeat(vv, g, axis=1)

        def chunk(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, 0)
            sc = _mm("qhd,khd->hqk", qi * dh ** -0.5, k, precision)
            qpos = i * qc + jnp.arange(qc)
            mask = jnp.arange(s)[None, :] <= qpos[:, None]
            sc = jnp.where(mask[None], sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            return _mm("hqk,khd->qhd", p, vv, precision)

        out = jax.lax.map(chunk, jnp.arange(s // qc))
        return out.reshape(s, h, dh)

    def layer(root, x, idx):
        w = layer_weights(c, root, idx)
        hn = _rms(x, eps) * w["norm1"]
        q = _rope(_mm("nsd,dk->nsk", hn, w["attn/wq"], precision)
                  .reshape(n, s, h, dh), theta)
        k = _rope(_mm("nsd,dk->nsk", hn, w["attn/wk"], precision)
                  .reshape(n, s, kv, dh), theta)
        vv = _mm("nsd,dk->nsk", hn, w["attn/wv"], precision).reshape(
            n, s, kv, dh)
        att = jax.lax.map(lambda a: attend(*a), (q, k, vv))
        x = x + _mm("nsk,kd->nsd", att.reshape(n, s, h * dh), w["attn/wo"],
                    precision)
        hn = _rms(x, eps) * w["norm2"]
        gu = _mm("nsd,dF->nsF", hn, w["mlp/wi"], precision)
        gate, up = jnp.split(gu, 2, axis=-1)
        x = x + _mm("nsf,fd->nsd", jax.nn.silu(gate) * up, w["mlp/wo"],
                    precision)
        return x, None

    def forward(root, tokens):
        outer = outer_weights(c, root)
        x = outer["embed"][tokens]
        x, _ = jax.lax.scan(lambda x, i: layer(root, x, i), x,
                            jnp.arange(n_layers))
        x = _rms(x[:, start:], eps) * outer["final_norm"]
        return _mm("nsd,dv->nsv", x, outer["head"], precision)

    with jax.default_matmul_precision("highest"):
        return jax.jit(forward)(root, tokens)


def logit_gaps(ref_logits, tokens) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best at its position: ``max(ref) - ref[token]``, float64."""
    ref = np.asarray(ref_logits, np.float64)
    tok = np.asarray(tokens)
    best = ref.max(axis=-1)
    got = np.take_along_axis(ref, tok[..., None], axis=-1)[..., 0]
    return best - got
