"""report.place_s: seconds per report placing the schedules' bytes into
the matrix and the per-primitive matrices (self time of the program's
``view.matrix`` and ``view.per_primitive`` spans reached from
``report()``: the decomposition under them is report.schedule_s's)."""
import spanlog


def read(run):
    w = spanlog.reports(run)
    if w is None:
        return None
    return w.self_seconds(("view.matrix", "view.per_primitive"),
                          outside="export.") / run.reports
