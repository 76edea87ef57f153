"""Serve a small LM with batched requests and a monitored serve session.

Uses the qwen3-family reduced config on a (data=4, model=2) mesh: prefill
the prompt batch, decode N tokens, then monitor prefill AND decode as the
two named phases of one :class:`MonitorSession` -- the per-phase tables
show the prefill all-gather-heavy profile next to the decode TP-psum
profile (the same cells ``python -m repro sweep --configs serve
--by-phase`` sweeps).

Run:  PYTHONPATH=src python examples/serve_lm.py [--tokens 24]
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import argparse
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro import configs
from repro.compat import make_mesh
from repro.launch.serve import monitor
from repro.models import build_model
from repro.parallel import Sharder
from repro.serve import ServeConfig, generate, make_serve_steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_8b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=24)
    args = ap.parse_args()

    mesh = make_mesh((4, 2), ("data", "model"))
    shd = Sharder(mesh)
    cfg = configs.config(args.arch, reduced=True)
    model = build_model(cfg)
    params_sh = shd.tree_shardings(model.shapes(), model.axes())
    params = jax.device_put(model.init(jax.random.PRNGKey(0)), params_sh)

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    max_len = args.prompt_len + args.tokens
    steps = make_serve_steps(model, shd, ServeConfig(max_len=max_len,
                                                     batch=args.batch),
                             params_sh)
    t0 = time.perf_counter()
    out, _ = generate(model, params, prompts, shd, steps=args.tokens,
                      max_len=max_len, serve_steps=steps)
    dt = time.perf_counter() - t0
    print(f"served {args.batch} requests x {args.tokens} tokens in {dt:.1f}s "
          f"({args.batch*args.tokens/dt:.1f} tok/s incl. compile)")
    print("sample completion ids:", out[0, :12].tolist())

    # prefill/decode communication profile of the served steps: one
    # two-phase session over ShapeDtypeStruct stand-ins (nothing executes)
    sess = monitor(model, steps, mesh, args.batch, args.prompt_len,
                   name=f"serve[{cfg.name}]")
    rep = sess.report()
    print()
    print(rep.phase_table())
    print(rep.phase_diff("prefill", "decode"))
    print(rep.heatmap(phase="decode"))
    print("serving example OK")


if __name__ == "__main__":
    main()
