"""capture.mlir_s: seconds per report JAX spent lowering the captured
jaxprs to MLIR modules: its to-MLIR events under the program's
``capture.lower`` spans."""
import spanlog


def read(run):
    w = spanlog.reports(run)
    if w is None:
        return None
    return w.jax_seconds((spanlog.MLIR,), ("capture.lower",)) / run.reports
