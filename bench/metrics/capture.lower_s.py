"""capture.lower_s: seconds per report spent tracing and lowering the
captured programs (the sum of ``Capture.trace_seconds`` of a report)."""


def read(run):
    return run.per_report("lower_s")
