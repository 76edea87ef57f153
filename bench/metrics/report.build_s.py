"""report.build_s: seconds per report in ``MonitorSession.report()``:
views, decomposition, placement and summaries."""


def read(run):
    return run.per_report("build_s")
