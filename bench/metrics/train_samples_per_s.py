"""train_samples_per_s: the samples of every training step completed in
the window, over the summed time of its job segments."""


def read(run):
    return run.counts["samples"] / run.job_s if run.job_s else None
