"""report.export_json_s: seconds per report writing it as JSON (the
program's ``export.json`` spans)."""
import spanlog


def read(run):
    w = spanlog.reports(run)
    if w is None:
        return None
    return w.seconds(("export.json",)) / run.reports
