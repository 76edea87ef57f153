"""capture.compile_s: seconds per report spent compiling the captured
programs, from the compile cache when warm (``Capture.compile_seconds``)."""


def read(run):
    return run.per_report("compile_s")
