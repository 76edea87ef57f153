"""train.mfu: forward and backward operations per sample (from shapes, in
the configuration's file) times samples per second over the job segments,
as a share of the bf16 peak of every chip in use (%)."""


def read(run):
    if not run.peaks or not run.job_s:
        return None
    return (100.0 * run.counts["train_flops"] / run.job_s
            / (run.cell.chips * run.peaks["bf16_flops_per_s"]))
