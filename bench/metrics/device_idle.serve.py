"""device_idle.serve: 1 - busy / window over the traced serving job
segments (%): busy is the union of the device's operations, averaged over
chips; the window is the segments' host-clock length."""


def read(run):
    t = run.trace
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
