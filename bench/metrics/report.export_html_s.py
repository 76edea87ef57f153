"""report.export_html_s: seconds per report writing it as HTML (the
program's ``export.html`` spans)."""
import spanlog


def read(run):
    w = spanlog.reports(run)
    if w is None:
        return None
    return w.seconds(("export.html",)) / run.reports
