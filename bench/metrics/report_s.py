"""report_s: the summed time of the window's monitor segments (capture,
report, JSON and HTML written) over the reports completed (host clock)."""


def read(run):
    return run.monitor_s / run.reports if run.reports else None
