"""report.export_s: seconds per report writing it as JSON and HTML."""


def read(run):
    return run.per_report("export_s")
