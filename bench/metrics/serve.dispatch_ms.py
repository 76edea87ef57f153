"""serve.dispatch_ms: host milliseconds per decode step: the program's
``serve.decode`` (the decode call) and ``serve.sample`` (slice, cast,
argmax and the next token's transfer) spans, less the JAX compile-path
events under them, over the decode steps they counted."""
import spanlog


def read(run):
    w = spanlog.batches(run)
    if w is None:
        return None
    steps = w.counter("serve.decode_steps")
    if not steps:
        return None
    spans = ("serve.decode", "serve.sample")
    host = w.seconds(spans) - w.jax_seconds(spanlog.JAX_EVENTS, spans)
    return 1e3 * host / steps
