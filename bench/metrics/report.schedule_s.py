"""report.schedule_s: seconds per report decomposing the collectives into
phase schedules, once per distinct shape (the program's ``view.schedule``
spans reached from ``report()``, not from the exports)."""
import spanlog


def read(run):
    w = spanlog.reports(run)
    if w is None:
        return None
    return w.seconds(("view.schedule",), outside="export.") / run.reports
