"""A run whose timed path is broken underneath reads ``correct`` false:
the harness is driven on the CPU at a small size, past its look for a
chip, with one fault planted in the program for each test."""
import jax
import pytest

import tiny

SERVE = "granite-3-2b.decode-b32"
DDP = "resnet18-ddp.4chip"


@pytest.fixture
def kernel(monkeypatch):
    tiny.interpret_kernel(monkeypatch)


def test_sound_serving_run_is_correct(kernel):
    assert tiny.run_tiny(tiny.tiny_cell(SERVE))["correct"]


def test_served_token_altered(kernel, monkeypatch):
    from repro.launch import serve
    real = serve.serve

    def altered(model, params, shd, prompts, tokens, steps):
        toks, logits = real(model, params, shd, prompts, tokens, steps)
        vocab = model.cfg.vocab_size
        return toks.at[:, 1].set((toks[:, 1] + vocab // 2) % vocab), logits

    monkeypatch.setattr(serve, "serve", altered)
    result = tiny.run_tiny(tiny.tiny_cell(SERVE))
    assert not result["correct"]
    assert result["checks"]["logit_gap"]["value"] > result["checks"][
        "logit_gap"]["limit"]


def test_decode_returns_its_cache_unchanged(kernel, monkeypatch):
    from repro.launch import serve
    real = serve.serve_steps

    def steps(*a, **kw):
        prefill, decode = real(*a, **kw)
        return prefill, jax.jit(lambda p, c, b: (decode(p, c, b)[0], c))

    monkeypatch.setattr(serve, "serve_steps", steps)
    assert not tiny.run_tiny(tiny.tiny_cell(SERVE))["correct"]


def test_sound_training_run_is_correct():
    assert tiny.run_tiny(tiny.tiny_cell(DDP))["correct"]


def test_step_returns_its_state_unchanged(monkeypatch):
    from repro.train import ddp
    real = ddp.make_ddp_train_step

    def make(*a, **kw):
        step = real(*a, **kw)
        return jax.jit(lambda p, e, b: (p, e, step(p, e, b)[2]))

    monkeypatch.setattr(ddp, "make_ddp_train_step", make)
    assert not tiny.run_tiny(tiny.tiny_cell(DDP))["correct"]


def test_half_the_batch_left_out(monkeypatch):
    from repro.models import resnet
    real = resnet.ResNet18.loss_fn

    def loss_fn(self, params, batch, shd=None, remat=None):
        half = batch["labels"].shape[0] // 2
        return real(self, params, jax.tree.map(lambda t: t[:half], batch))

    monkeypatch.setattr(resnet.ResNet18, "loss_fn", loss_fn)
    assert not tiny.run_tiny(tiny.tiny_cell(DDP))["correct"]


def test_gradient_exchange_left_out(monkeypatch):
    from repro.train import ddp
    monkeypatch.setattr(ddp, "allreduce_bucketed",
                        lambda grads, *a, error_feedback=None, **kw:
                        (grads, error_feedback))
    result = tiny.run_tiny(tiny.tiny_cell(DDP))
    assert not result["correct"]
