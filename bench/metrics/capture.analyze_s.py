"""capture.analyze_s: seconds per report parsing the HLO text for its
collectives and their loop weights (the program's ``capture.analyze_hlo``
spans)."""
import spanlog


def read(run):
    w = spanlog.reports(run)
    if w is None:
        return None
    return w.seconds(("capture.analyze_hlo",)) / run.reports
