"""capture.analyses_s: seconds per report in the compiler's cost and
memory analyses of the captured programs (the program's
``capture.analyses`` spans)."""
import spanlog


def read(run):
    w = spanlog.reports(run)
    if w is None:
        return None
    return w.seconds(("capture.analyses",)) / run.reports
