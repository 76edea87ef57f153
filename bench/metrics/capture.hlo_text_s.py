"""capture.hlo_text_s: seconds per report printing the compiled modules
as HLO text (the program's ``capture.hlo_text`` spans)."""
import spanlog


def read(run):
    w = spanlog.reports(run)
    if w is None:
        return None
    return w.seconds(("capture.hlo_text",)) / run.reports
