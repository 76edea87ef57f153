"""serve.retrace_s: seconds per cycle the served job spent tracing,
lowering, compiling or reading its programs from the compile cache: the
JAX compile-path events under the program's ``serve.*`` spans, counted
once where they nest.  Each capture clears the job's jitted steps, so
this is what the monitor costs the job on the host."""
import spanlog


def read(run):
    w = spanlog.batches(run)
    if w is None or not run.cycles:
        return None
    return w.jax_seconds(spanlog.JAX_EVENTS, spanlog.SERVE_SPANS) / run.cycles
