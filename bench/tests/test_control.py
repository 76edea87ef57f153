"""The control (the plain reference in the next precision down: fp8
matmuls for the bf16 server, bfloat16 for the float32 trainer) reads far
above the program, on the CPU at sizes a test run holds.  On the chip the
same readings, at the cells' own sizes, set the limits (bench/control.py,
PERF.md)."""
import dataclasses

import jax
import pytest

import control
import tiny

SERVE_SIZE = {"num_hidden_layers": 4, "hidden_size": 256,
              "intermediate_size": 512, "num_attention_heads": 4,
              "num_key_value_heads": 2, "vocab_size": 8192,
              "attention_multiplier": 0.125}
SERVE_TRAFFIC = {"batch": 4, "prompt_len": 32, "gen_tokens": 16,
                 "check_requests": 4}


@pytest.mark.parametrize("seed", [1, 2])
def test_serving_control_reads_far_above_the_program(monkeypatch, seed):
    tiny.interpret_kernel(monkeypatch)
    cell = tiny.tiny_cell("granite-3-2b.decode-b32")
    cell = dataclasses.replace(
        cell, config=dict(cell.config, **SERVE_SIZE),
        traffic=dict(cell.traffic, **SERVE_TRAFFIC))
    r = control.serve_readings(cell, seed, jax.devices()[:1])
    assert r["control.logit_gap"] >= max(3 * r["logit_gap"], 0.1)


def test_training_control_and_faults_read_far_above_the_program():
    cell = tiny.tiny_cell("resnet18-ddp.4chip")
    r = control.ddp_readings(cell, 5, jax.devices()[:4])
    for k in ("grad_gap", "change_gap"):
        assert r[f"control.{k}"] >= 10 * r[k]
        assert r[f"half_batch.{k}"] >= 10 * r[k]
        assert r[f"no_exchange.{k}"] >= 10 * r[k]
