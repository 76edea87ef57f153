"""Small cells for CPU tests: the real cells' files with sizes cut so a
run takes seconds, the Pallas kernel in interpret mode."""
from __future__ import annotations

import dataclasses
import json
import os

import harness

GRANITE_TINY = {"num_hidden_layers": 2, "hidden_size": 64,
                "intermediate_size": 128, "num_attention_heads": 4,
                "num_key_value_heads": 2, "vocab_size": 512,
                "attention_multiplier": 0.25}
SERVE_TINY = {"batch": 2, "prompt_len": 8, "gen_tokens": 16,
              "feed_batches": 2, "units_per_cycle": 1, "check_requests": 2}
DDP_TINY = {"image_size": 16, "global_batch": 8}
DDP_TRAFFIC_TINY = {"feed_batches": 4, "units_per_cycle": 2}


# The four-chip DDP cell's entries, kept out of BENCHMARK.json until the
# cell has been measured on the chip (PERF.md, Open questions).
DDP_ENTRIES = {
    "configs": [{"name": "resnet18-ddp",
                 "source": "https://arxiv.org/abs/2110.10401",
                 "file": "bench/configs/resnet18-ddp.json",
                 "reduced": ["stem", "norm"], "why": "the paper's DDP app"}],
    "workloads": [{"name": "resnet18-ddp.4chip", "config": "resnet18-ddp",
                   "traffic": "global-b64", "chips": 4, "why": "DDP"}],
    "end_to_end": [{"name": "train_samples_per_s", "unit": "samples/s",
                    "better": "higher", "bound": 0.25,
                    "source": "host_clock",
                    "workloads": ["resnet18-ddp.4chip"]}],
    "per_layer": [{"name": n, "unit": u, "better": b, "source": s,
                   "layer": layer, "moves": "train_samples_per_s",
                   "workloads": ["resnet18-ddp.4chip"]}
                  for n, u, b, s, layer in (
                      ("train.mfu", "%", "higher", "host_clock",
                       "model step"),
                      ("allreduce.device_ms", "ms/step", "lower",
                       "device_trace", "collectives"),
                      ("device_idle.train", "%", "lower", "device_trace",
                       "device"))],
}


def spec_with_ddp() -> dict:
    spec = json.load(open(os.path.join(harness.CHECKOUT, "BENCHMARK.json")))
    for key, entries in DDP_ENTRIES.items():
        spec[key] = spec[key] + entries
    return spec


def load_cell(workload: str) -> harness.Cell:
    return harness.load_cell(workload, spec_with_ddp())


def tiny_cell(workload: str, limits: dict | None = None) -> harness.Cell:
    cell = load_cell(workload)
    if cell.traffic["driver"] == "serve":
        config = dict(cell.config, **GRANITE_TINY)
        traffic = dict(cell.traffic, **SERVE_TINY)
    else:
        config = dict(cell.config, **DDP_TINY)
        traffic = dict(cell.traffic, **DDP_TRAFFIC_TINY)
    return dataclasses.replace(cell, config=config, traffic=traffic,
                               limits=limits or cell.limits)


def interpret_kernel(monkeypatch) -> None:
    """Route the program's attention through the Pallas kernel in
    interpret mode, as a TPU would run it compiled."""
    from repro.kernels.flash_attention import ops
    real = ops.attend

    def attend(q, k, v, **kw):
        kw.setdefault("force", "pallas_interpret")
        return real(q, k, v, **kw)

    monkeypatch.setattr(ops, "attend", attend)


def run_tiny(cell: harness.Cell, seed: int = 3, seconds: float = 0.0,
             chips: int | None = None) -> dict:
    import jax
    devices = jax.devices()[:chips or cell.chips]
    return harness.run_cell(cell, seed, seconds, False, devices,
                            harness.now(), None)
