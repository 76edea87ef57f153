"""capture.parse_s: seconds per report inside the capture call but outside
lowering and compiling: shape inference of the captured inputs, the HLO
text, its parse, and the compiler's cost and memory analyses."""


def read(run):
    if not run.spans:
        return None
    return sum(s["capture_s"] - s["lower_s"] - s["compile_s"]
               for s in run.spans) / len(run.spans)
