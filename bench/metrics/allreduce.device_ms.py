"""allreduce.device_ms: device time of the all-reduce operations per
training step, from the trace, averaged over chips (ms/step)."""
import trace_reduce


def read(run):
    if not run.trace or not run.traced_counts.get("steps"):
        return None
    t = trace_reduce.ops_time(run.trace, "all-reduce")
    if not t:
        return None
    return 1e3 * t / run.traced_counts["steps"]
