"""report.place_us_per_edge: microseconds placing one edge of the
schedules' bytes into the matrix and the per-primitive matrices: the self
time of the program's ``view.matrix`` and ``view.per_primitive`` spans
reached from ``report()`` (not from the exports), over the edges they
counted (``view.edges``).  Reads nothing where no edge was placed, as on a
report without collectives."""
import spanlog


def read(run):
    w = spanlog.reports(run)
    if w is None:
        return None
    names = ("view.matrix", "view.per_primitive")
    edges = sum((r["counts"] or {}).get("view.edges", 0)
                for r in w.named(names, outside="export."))
    if not edges:
        return None
    return 1e6 * w.self_seconds(names, outside="export.") / edges
