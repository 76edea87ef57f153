"""decode_roofline: the least bytes the traced decode steps need (each
weight once, the cached keys and values up to each position, the new
entry) over the decode program's device time, as a share of HBM peak (%).

The decode program is the ``jit_step`` program (the serving launcher's
jitted steps are both named ``step``) that ran once per decode step of the
traced segments; where none ran exactly that often, nothing is read."""
import trace_reduce


def read(run):
    if not run.trace or not run.peaks:
        return None
    t = trace_reduce.module_time(run.trace, run.traced_counts["decode_steps"],
                                  "jit_step(")
    if not t:
        return None
    return (100.0 * run.traced_counts["decode_bytes"] / t
            / run.peaks["hbm_bytes_per_s"])
