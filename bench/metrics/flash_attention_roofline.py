"""flash_attention_roofline: the least time the traced prefills' causal
attention needs at the chip's peaks, max(FLOPs / bf16 peak, bytes / HBM
peak) from shapes, over the Pallas kernel's device time (%)."""
import trace_reduce


def read(run):
    if not run.trace or not run.peaks:
        return None
    t = trace_reduce.ops_time(run.trace, "flash_attention")
    if not t:
        return None
    least = max(run.traced_counts["attention_flops"]
                / run.peaks["bf16_flops_per_s"],
                run.traced_counts["attention_bytes"]
                / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
