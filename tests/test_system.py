"""End-to-end system behaviour: drivers, serving, monitor integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.compile   # whole module drives XLA compiles


class TestTrainDriver:
    def test_train_resume_identical(self, tmp_path):
        """Fault tolerance: crash at step 10 + resume == uninterrupted run."""
        from repro.launch.train import main
        base = ["--arch", "granite_3_2b", "--global-batch", "4",
                "--seq-len", "16", "--mesh", "4x2", "--ckpt-every", "10"]
        full = main(base + ["--steps", "20",
                            "--ckpt-dir", str(tmp_path / "a")])
        # run that "crashes" after step 10, then restarts from its checkpoint
        main(base + ["--steps", "10", "--ckpt-dir", str(tmp_path / "b")])
        resumed = main(base + ["--steps", "20", "--resume",
                               "--ckpt-dir", str(tmp_path / "b")])
        assert resumed[-1] == pytest.approx(full[-1], rel=1e-4)

    def test_serve_driver_generates(self):
        from repro.launch.serve import main
        out = main(["--arch", "granite_3_2b", "--batch", "2",
                    "--prompt-len", "8", "--tokens", "4", "--mesh", "4x2"])
        assert out.shape == (2, 4)


class TestCompileCache:
    """One persistent-cache location: $JAX_COMPILATION_CACHE_DIR when set,
    else the fixed <checkout>/.jax_cache."""

    @pytest.fixture
    def restore_cache_dir(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_checkout_dir_when_unset(self, monkeypatch, restore_cache_dir):
        import os
        from repro.compat import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = enable_compile_cache()
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        assert path == os.path.join(checkout, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path

    def test_env_dir_wins(self, monkeypatch, tmp_path, restore_cache_dir):
        from repro.compat import enable_compile_cache
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)


class TestServing:
    def test_greedy_generation_deterministic(self, mesh8):
        from repro import configs
        from repro.models import build_model
        from repro.parallel import Sharder
        from repro.serve import generate
        shd = Sharder(mesh8)
        cfg = configs.config("qwen3_8b", reduced=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                     cfg.vocab_size)
        a, logits_a = generate(model, params, prompts, shd, steps=6,
                               max_len=32)
        b, logits_b = generate(model, params, prompts, shd, steps=6,
                               max_len=32)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(logits_a),
                                      np.asarray(logits_b))
        # greedy: every token is the argmax of the logits it came from
        assert logits_a.shape == (2, 6, cfg.vocab_size)
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(logits_a.argmax(-1)))

    def test_monitor_captures_the_served_steps(self):
        """The launcher's report describes the program it served: each
        capture is the served step's own lowering, with its shardings."""
        from repro.core import hlo_cost
        from repro.core.session import _cost_analysis, _memory_stats
        from repro.launch import serve
        from repro.serve.serve import token_sharding
        args = serve.parse_args(["--arch", "granite_3_2b", "--mesh", "2x2",
                                 "--batch", "2", "--prompt-len", "8",
                                 "--tokens", "3"])
        mesh, shd, model, params = serve.build(args)
        prompts = serve.prompts_for(args, model.cfg.vocab_size)
        steps = serve.serve_steps(model, shd, args)
        out, _ = serve.serve(model, params, shd, prompts, args.tokens, steps)
        assert out.shape == (2, 3)
        prefill, decode = steps
        tok_sh = token_sharding(shd, args.batch)
        batch = {"tokens": jax.device_put(prompts, tok_sh)}
        _, cache = prefill(params, batch)
        served = [prefill.lower(params, batch), decode.lower(
            params, cache, {"tokens": jax.device_put(out[:, :1], tok_sh)})]

        sess = serve.monitor(model, steps, mesh, args.batch, args.prompt_len,
                             name="serve")
        assert sess.phase_names() == ["prefill", "decode"]
        # what the report says of each program: its collectives, its cost
        # and its memory, as the served step's own compile gives them
        for cap, lowered in zip(sess.captures, served):
            compiled = lowered.compile()
            ops = hlo_cost.analyze_hlo(compiled.as_text()).collectives
            assert ([(op.kind, op.payload_bytes, op.replica_groups)
                     for op in cap.ops]
                    == [(op.kind, op.payload_bytes, op.replica_groups)
                        for op in ops]), cap.name
            assert cap.cost == _cost_analysis(compiled), cap.name
            assert cap.memory_stats == _memory_stats(compiled), cap.name
        assert any(cap.ops for cap in sess.captures)


class TestConfigs:
    def test_registry_complete(self):
        from repro import configs
        assert len(configs.ARCH_IDS) == 10
        for arch in configs.ARCH_IDS:
            cfg = configs.config(arch)
            assert cfg.n_layers > 0 and cfg.vocab_size > 0
            red = configs.config(arch, reduced=True)
            assert red.d_model <= 128

    def test_cells_skip_long_for_full_attention(self):
        from repro import configs
        cells = configs.cells()
        long_archs = {a for a, s in cells if s == "long_500k"}
        assert long_archs == {"xlstm_1_3b", "recurrentgemma_2b"}
        # 10 archs x 3 shapes + 2 long = 32 runnable cells
        assert len(cells) == 32

    def test_input_specs_match_shapes(self):
        from repro import configs
        from repro.models.common import SHAPES_BY_NAME
        cfg = configs.config("chameleon_34b")
        spec = configs.input_specs(cfg, SHAPES_BY_NAME["train_4k"])
        assert spec["embeds"].shape == (256, 4096, 8192)
        spec = configs.input_specs(cfg, SHAPES_BY_NAME["decode_32k"])
        assert spec["tokens"].shape == (128, 1)
