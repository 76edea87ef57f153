"""Serving launcher: batched prefill + greedy decode, with the prefill and the
decode monitored as the two phases of one :class:`MonitorSession`.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_8b --tokens 32
  PYTHONPATH=src python -m repro.launch.serve --arch granite_3_2b \\
      --published --mesh 1 --prompt-len 512 --report serve.json

``--published`` builds the architecture's published configuration
(``CONFIG``) instead of its reduced smoke-test preset; ``chip_smoke.py``
drives it through :func:`build`, :func:`serve_steps`, :func:`serve` and
:func:`monitor`.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.compat import enable_compile_cache
from repro.core import MonitorSession
from repro.launch.mesh import make_test_mesh
from repro.models import build_model
from repro.parallel import Sharder
from repro.serve import ServeConfig, generate, make_serve_steps


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_8b")
    ap.add_argument("--published", action="store_true",
                    help="the published config (default: the reduced one)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--mesh", default="2x2")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--report", default="",
                    help="write the two-phase CommReport JSON here")
    return ap.parse_args(argv)


def build(args):
    """``(mesh, shd, model, params)``: random weights from ``--seed``,
    initialized in place with their shardings (no host round trip)."""
    shape = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_test_mesh(shape, ("data", "model")[:len(shape)])
    shd = Sharder(mesh)
    model = build_model(configs.config(args.arch,
                                       reduced=not args.published))
    params_sh = shd.tree_shardings(model.shapes(), model.axes())
    params = jax.jit(model.init, out_shardings=params_sh)(
        jax.random.PRNGKey(args.seed))
    return mesh, shd, model, params


def prompts_for(args, vocab_size: int):
    """``(batch, prompt_len)`` token ids drawn from ``--seed``."""
    return jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                              (args.batch, args.prompt_len), 0, vocab_size)


def serve_steps(model, shd, args):
    """The jitted ``(prefill, decode)`` that :func:`serve` runs and
    :func:`monitor` captures, built once with the parameters' shardings."""
    scfg = ServeConfig(max_len=args.prompt_len + args.tokens, batch=args.batch)
    params_sh = shd.tree_shardings(model.shapes(), model.axes())
    return make_serve_steps(model, shd, scfg, params_sh)


def serve(model, params, shd, prompts, tokens: int, steps):
    """Greedy-decode ``tokens`` per prompt with ``steps`` (from
    :func:`serve_steps`): ``(tokens, logits)``."""
    return generate(model, params, prompts, shd, steps=tokens,
                    max_len=prompts.shape[1] + tokens, serve_steps=steps)


def monitor(model, steps, mesh, batch: int, prompt_len: int, name: str):
    """The served ``steps`` captured as the phases ``prefill`` and
    ``decode`` of one session, over shape stand-ins (nothing executes)."""
    prefill, decode = steps
    params = model.shapes()
    prompt = {"tokens": jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)}
    # the cache exactly as the served prefill hands it to decode
    _, cache = jax.eval_shape(prefill, params, prompt)
    sess = MonitorSession(mesh=mesh, name=name)
    with sess.phase("prefill"):
        sess.capture(prefill, params, prompt, name="prefill")
    with sess.phase("decode"):
        sess.capture(decode, params, cache,
                     {"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32)},
                     name="decode")
    return sess


def main(argv=None):
    args = parse_args(argv)
    mesh, shd, model, params = build(args)
    prompts = prompts_for(args, model.cfg.vocab_size)
    t0 = time.perf_counter()
    steps = serve_steps(model, shd, args)
    out, _ = serve(model, params, shd, prompts, args.tokens, steps)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    tps = args.batch * args.tokens / dt
    print(f"[serve] generated {out.shape} in {dt:.2f}s ({tps:.1f} tok/s, "
          f"compile included)")
    print("[serve] sample:", out[0, :16].tolist())

    if args.report:
        rep = monitor(model, steps, mesh, args.batch, args.prompt_len,
                      f"serve[{args.arch}]").report()
        print(rep.render())
        rep.save(args.report)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    main()
