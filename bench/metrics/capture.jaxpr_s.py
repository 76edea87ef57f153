"""capture.jaxpr_s: seconds per report JAX spent tracing the captured
functions to jaxprs: its jaxpr-trace events under the program's
``capture.lower`` spans, nested traces counted once."""
import spanlog


def read(run):
    w = spanlog.reports(run)
    if w is None:
        return None
    return w.jax_seconds((spanlog.JAXPR,), ("capture.lower",)) / run.reports
