"""Per-architecture smoke tests: reduced configs, one forward/train step on
CPU, shape + finiteness asserts; prefill-vs-decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model
from repro.parallel import Sharder
from repro.compat import make_mesh

pytestmark = pytest.mark.compile   # whole module drives XLA compiles

ARCHS = list(configs.ARCH_IDS)


def make_batch(cfg, b=2, s=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    batch = {"tokens": jax.random.randint(ks[0], (b, s), 0, cfg.vocab_size),
             "labels": jax.random.randint(ks[1], (b, s), 0, cfg.vocab_size)}
    if cfg.input_mode == "embeddings":
        batch["embeds"] = jax.random.normal(
            ks[2], (b, s, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.fixture(scope="module")
def shd(mesh8):
    return Sharder(mesh8)


@pytest.mark.parametrize("arch", ARCHS)
class TestArchSmoke:
    def test_forward_and_train_step(self, arch, shd):
        cfg = configs.config(arch, reduced=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = make_batch(cfg)

        def loss(p, b):
            return model.loss_fn(p, b, shd)[0]

        val, grads = jax.jit(jax.value_and_grad(loss))(params, batch)
        assert jnp.isfinite(val), f"{arch}: loss not finite"
        # gradient step moves the loss
        p2 = jax.tree.map(lambda p, g: p - 0.3 * g.astype(p.dtype),
                          params, grads)
        val2 = jax.jit(loss)(p2, batch)
        assert jnp.isfinite(val2)
        assert float(val2) < float(val), f"{arch}: grad step didn't descend"
        # gradient structure matches params; every leaf finite
        for g in jax.tree.leaves(grads):
            assert jnp.all(jnp.isfinite(g.astype(jnp.float32)))

    def test_decode_step_shapes(self, arch, shd):
        cfg = configs.config(arch, reduced=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        b = 2
        cache = model.init_cache(b, 16)
        batch = {"tokens": jnp.zeros((b, 1), jnp.int32)}
        if cfg.input_mode == "embeddings":
            batch["embeds"] = jnp.zeros((b, 1, cfg.d_model), jnp.bfloat16)
        logits, cache2 = jax.jit(
            lambda p, c, bb: model.decode_step(p, c, bb, shd))(
            params, cache, batch)
        assert logits.shape == (b, 1, cfg.vocab_size)
        assert jnp.all(jnp.isfinite(logits.astype(jnp.float32)))
        assert int(cache2["len"]) == 1

    def test_full_config_param_count_sane(self, arch, shd):
        cfg = configs.config(arch)
        model = build_model(cfg)
        from repro.models.common import count_params
        n = count_params(model.specs())
        # within 3x of the architecture's nameplate (approximations OK)
        names = {"grok_1_314b": 314e9, "llama4_maverick_400b_a17b": 400e9,
                 "codeqwen15_7b": 7e9, "granite_3_2b": 2.5e9,
                 "qwen3_8b": 8e9, "granite_20b": 20e9, "xlstm_1_3b": 1.3e9,
                 "chameleon_34b": 34e9, "musicgen_medium": 1.5e9,
                 "recurrentgemma_2b": 2.7e9}
        nameplate = names[arch]
        assert nameplate / 3 < n < nameplate * 3, \
            f"{arch}: {n/1e9:.1f}B vs nameplate {nameplate/1e9:.0f}B"


class TestPrefillDecodeConsistency:
    """Prefill(tokens) must equal step-by-step decode — the strongest
    correctness property linking the parallel and recurrent forms."""

    @pytest.mark.parametrize("arch,window", [
        pytest.param("granite_3_2b", 0, id="granite_3_2b"),
        pytest.param("qwen3_8b", 0, id="qwen3_8b"),
        pytest.param("xlstm_1_3b", 0, id="xlstm_1_3b"),
        pytest.param("recurrentgemma_2b", 0, id="recurrentgemma_2b"),
        # a sliding window of 4 over 8 tokens: the ring slot wraps twice
        pytest.param("granite_3_2b", 4, id="granite_3_2b-window4"),
    ])
    def test_prefill_matches_stepwise_decode(self, arch, window, shd):
        import dataclasses
        # fp32 compute so the tolerance tests logic, not bf16 rounding
        cfg = dataclasses.replace(configs.config(arch, reduced=True),
                                  compute_dtype="float32")
        if window:
            cfg = dataclasses.replace(cfg, attn_window=window)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(1))
        b, s = 2, 8
        toks = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0,
                                  cfg.vocab_size)
        pf_logits, _ = jax.jit(
            lambda p, bb: model.prefill(p, bb, shd))(params, {"tokens": toks})

        cache = model.init_cache(b, s)
        # the cache donated, as the served decode takes it: each step
        # consumes the one the step before returned
        step = jax.jit(lambda p, c, bb: model.decode_step(p, c, bb, shd),
                       donate_argnums=1)
        for t in range(s):
            old = cache
            logits, cache = step(params, cache, {"tokens": toks[:, t:t + 1]})
            if t:
                assert all(a.is_deleted() for a in jax.tree.leaves(old))
        np.testing.assert_allclose(
            np.asarray(pf_logits, np.float32),
            np.asarray(logits[:, 0], np.float32), rtol=2e-2, atol=2e-2)


class TestXLSTMMath:
    def test_mlstm_parallel_equals_sequential(self):
        from repro.models.xlstm import (mlstm_decode_step, mlstm_final_state,
                                        mlstm_parallel)
        b, s, nh, dh = 2, 24, 2, 8
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        q = jax.random.normal(ks[0], (b, s, nh, dh))
        k = jax.random.normal(ks[1], (b, s, nh, dh))
        v = jax.random.normal(ks[2], (b, s, nh, dh))
        log_f = jax.nn.log_sigmoid(jax.random.normal(ks[3], (b, s, nh)) + 1)
        it = jax.random.normal(ks[4], (b, s, nh)) * 0.5

        par = mlstm_parallel(q, k, v, log_f, it, chunk=8)
        state = {"C": jnp.zeros((b, nh, dh, dh)),
                 "n": jnp.zeros((b, nh, dh)), "m": jnp.full((b, nh), -1e30)}
        outs = []
        for t in range(s):
            h, state = mlstm_decode_step(q[:, t], k[:, t], v[:, t],
                                         log_f[:, t], it[:, t], state)
            outs.append(h)
        seq = jnp.stack(outs, axis=1)
        assert jnp.max(jnp.abs(par - seq)) < 1e-4
        # final state from the closed form matches the recurrence (probe)
        fs = mlstm_final_state(k, v, log_f, it)
        probe = jax.random.normal(ks[0], (b, nh, dh))

        def read(st):
            num = jnp.einsum("bhde,bhe->bhd", st["C"], probe)
            den = jnp.abs(jnp.einsum("bhd,bhd->bh", st["n"], probe))
            return num / jnp.maximum(den, jnp.exp(-st["m"]))[..., None]

        assert jnp.max(jnp.abs(read(fs) - read(state))) < 1e-4

    def test_rglru_state_fold(self):
        """Splitting a sequence must equal processing it whole."""
        from repro.models.common import ModelConfig
        from repro.models.rglru import recurrent_block, rglru_spec, init_rec_state
        from repro.models.common import init_params
        from repro.parallel import Sharder
        import jax
        mesh = make_mesh((1,), ("data",))
        shd1 = Sharder(mesh)
        cfg = ModelConfig(name="t", family="hybrid", n_layers=1, d_model=32,
                          n_heads=2, n_kv_heads=1, d_ff=64, vocab_size=64,
                          attn_window=8, d_rnn=32)
        p = init_params(rglru_spec(cfg), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32),
                              jnp.float32)
        full, st_full = recurrent_block(p, x, cfg, shd1,
                                        state=init_rec_state(cfg, 2))
        st = init_rec_state(cfg, 2)
        o1, st = recurrent_block(p, x[:, :8], cfg, shd1, state=st)
        o2, st = recurrent_block(p, x[:, 8:], cfg, shd1, state=st)
        np.testing.assert_allclose(np.asarray(full[:, 8:]), np.asarray(o2),
                                   rtol=2e-3, atol=2e-3)
