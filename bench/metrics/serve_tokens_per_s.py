"""serve_tokens_per_s: every prompt and generated token of the requests
completed in the window, over the summed time of its job segments."""


def read(run):
    return run.counts["tokens"] / run.job_s if run.job_s else None
