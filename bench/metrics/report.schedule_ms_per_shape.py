"""report.schedule_ms_per_shape: milliseconds decomposing one distinct
collective shape into its phase schedule: the program's ``view.schedule``
spans reached from ``report()`` (not from the exports), over the distinct
shapes they counted (``view.shapes``).  Reads nothing where no shape was
decomposed, as on a report without collectives."""
import spanlog


def read(run):
    w = spanlog.reports(run)
    if w is None:
        return None
    names = ("view.schedule",)
    shapes = sum((r["counts"] or {}).get("view.shapes", 0)
                 for r in w.named(names, outside="export."))
    if not shapes:
        return None
    return 1e3 * w.seconds(names, outside="export.") / shapes
