"""serve.mfu: the operations that prefill and decode of the completed
requests need (from shapes, in the configuration's file), over the job
segments' time, as a share of the chip's bf16 peak (%)."""


def read(run):
    if not run.peaks or not run.job_s:
        return None
    flops = run.counts["prefill_flops"] + run.counts["decode_flops"]
    return 100.0 * flops / run.job_s / run.peaks["bf16_flops_per_s"]
