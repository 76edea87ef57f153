"""Serving layer: batched prefill + decode steps over sharded caches.

Decode-shape cells (``decode_32k``, ``long_500k``) lower ``serve_step`` — one
new token against a seq_len-deep cache.  Cache sharding comes from the same
logical-rules table as everything else: KV caches shard their sequence dim
over the model axis (context parallelism), recurrent states shard their
feature dim; batch shards over (pod, data).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import spans
from repro.parallel import Sharder


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int = 2048
    batch: int = 8
    cache_dtype: str = "bfloat16"
    temperature: float = 0.0             # 0 -> greedy


def cache_shardings(model, serve_cfg: ServeConfig, shd: Sharder):
    shapes = model.cache_shapes(serve_cfg.batch, serve_cfg.max_len,
                                serve_cfg.cache_dtype)
    axes = model.cache_axes()
    return shd.tree_shardings(shapes, axes)


def token_sharding(shd: Sharder, batch: int):
    """Where a ``(batch, n)`` token array lives: batch over the data axes."""
    return shd.named((batch, 1), ("batch", None))


def make_decode_step(model, shd: Sharder, serve_cfg: ServeConfig,
                     params_sh=None, donate_cache: bool = True,
                     batch_sh=None):
    """jit'd decode_step(params, cache, batch) -> (logits, cache)."""
    cache_sh = cache_shardings(model, serve_cfg, shd)

    def step(params, cache, batch):
        return model.decode_step(params, cache, batch, shd)

    kw = dict(in_shardings=(params_sh, cache_sh, batch_sh),
              out_shardings=(None, cache_sh))
    if donate_cache:
        kw["donate_argnums"] = (1,)
    return jax.jit(step, **kw), cache_sh


def make_prefill_step(model, shd: Sharder, serve_cfg: ServeConfig,
                      params_sh=None, batch_sh=None):
    cache_sh = cache_shardings(model, serve_cfg, shd)

    def step(params, batch):
        return model.prefill(params, batch, shd, max_len=serve_cfg.max_len)

    return jax.jit(step, in_shardings=(params_sh, batch_sh),
                   out_shardings=(None, cache_sh)), cache_sh


def make_serve_steps(model, shd: Sharder, serve_cfg: ServeConfig,
                     params_sh=None):
    """The jitted ``(prefill, decode)`` pair :func:`generate` runs; a
    monitor that captures these same objects reports the served program.
    Every input's sharding is pinned, so shape stand-ins lower to the
    program the live arguments run."""
    batch_sh = {"tokens": token_sharding(shd, serve_cfg.batch)}
    prefill, _ = make_prefill_step(model, shd, serve_cfg, params_sh, batch_sh)
    decode, _ = make_decode_step(model, shd, serve_cfg, params_sh,
                                 donate_cache=False, batch_sh=batch_sh)
    return prefill, decode


def generate(model, params, prompts, shd: Sharder, *, steps: int = 16,
             max_len: int = 256, rng=None, temperature: float = 0.0,
             serve_steps=None):
    """Greedy/temperature batched generation (launcher, examples, tests).

    ``serve_steps`` is a ``(prefill, decode)`` pair from
    :func:`make_serve_steps` (default: built here for ``max_len``).
    Returns ``(tokens, logits)``: the ``(B, steps)`` generated ids and the
    ``(B, steps, vocab)`` fp32 logits each was sampled from.
    """
    scfg = ServeConfig(max_len=max_len, batch=prompts.shape[0],
                       temperature=temperature)
    prefill, decode = serve_steps or make_serve_steps(model, shd, scfg)
    tok_sh = token_sharding(shd, scfg.batch)
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    def sample(logits, rng):
        if temperature > 0:
            return jax.random.categorical(rng, logits / temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    # the spans of one batch share a group (repro.core.spans)
    group = spans.new_group()
    with spans.span("serve.prefill", group):
        logits, cache = prefill(params,
                                {"tokens": jax.device_put(prompts, tok_sh)})
        logits = logits.astype(jnp.float32)
        tok = sample(logits, rng)
        batch = {"tokens": jax.device_put(tok[:, None], tok_sh)}
    toks, all_logits = [tok], [logits]
    for _ in range(steps - 1):
        with spans.span("serve.decode", group):
            spans.count("serve.decode_steps")
            logits, cache = decode(params, cache, batch)
        with spans.span("serve.sample", group):
            rng, k = jax.random.split(rng)
            logits = logits[:, -1].astype(jnp.float32)
            tok = sample(logits, k)
            batch = {"tokens": jax.device_put(tok[:, None], tok_sh)}
        toks.append(tok)
        all_logits.append(logits)
    return jnp.stack(toks, axis=1), jnp.stack(all_logits, axis=1)
