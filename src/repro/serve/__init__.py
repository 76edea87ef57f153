from .serve import (ServeConfig, make_prefill_step, make_decode_step,
                    make_serve_steps, cache_shardings, generate)

__all__ = ["ServeConfig", "make_prefill_step", "make_decode_step",
           "make_serve_steps", "cache_shardings", "generate"]
