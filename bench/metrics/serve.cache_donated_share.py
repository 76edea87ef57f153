"""serve.cache_donated_share: the share of the window's decode steps, in
%, whose decode call consumed the cache it was given (donated, so the step
wrote its new entries in place): the program's ``serve.cache_donated``
counter over its ``serve.decode_steps``.  A program without the counter
reads nothing."""
import spanlog


def read(run):
    w = spanlog.batches(run)
    if w is None:
        return None
    steps = w.counter("serve.decode_steps")
    donated = w.counter("serve.cache_donated")
    if not steps or not donated:
        return None
    return 100.0 * donated / steps
