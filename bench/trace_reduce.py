"""Reduction of the profiler's traces to the numbers the metrics read.

Each traced job segment leaves one ``.xplane.pb``; ``jax.profiler.
ProfileData`` reads it.  A chip is a plane named ``/device:<KIND>:<n>``
whose lines include ``XLA Ops``: every event there is one operation on the
device, and its name is the HLO instruction (``%fusion.86 = bf16[...]
fusion(...)``).  ``XLA Modules`` holds one event per program execution,
named ``jit_<fn>(<fingerprint>)``.  Host threads are the lines of the
``/host:CPU`` plane, on the same clock.

Per chip, and then averaged over the chips:
  busy     the union of the operations' intervals;
  modules  executions and device time of each program;
  ops      self time of each instruction (nested operations, such as a
           while loop's body, are subtracted from the one that holds them);
  gaps     the intervals between busy intervals, and the lead-in from the
           first of the harness's host spans to the first operation, each
           named by the innermost host event open on the benchmark's thread
           at the gap's middle.
"""
from __future__ import annotations

import glob
import os
import re

_DEVICE = re.compile(r"^/device:(?!CUSTOM)[A-Z]+:\d+$")
HOST_MARKS = ("serve_batch", "ddp_step")     # the harness's own spans


def options():
    """Profiler options for a traced job segment: host spans and device
    operations, without the Python tracer (which slows the host)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def instruction(name: str) -> str:
    """``%fusion.86 = bf16[...] fusion(...)`` -> ``fusion.86``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """Self time of each (name, start, end), children subtracted."""
    evs = sorted(events, key=lambda x: (x[1], -x[2]))
    self_ns = [e[2] - e[1] for e in evs]
    stack: list[int] = []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][2]:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    out: dict[str, float] = {}
    for (name, _, _), t in zip(evs, self_ns):
        key = instruction(name)
        out[key] = out.get(key, 0.0) + t
    return out


def _host_thread(planes):
    """The host line that carries the harness's spans."""
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if any(ev.name in HOST_MARKS for ev in line.events):
                return [(ev.name, ev.start_ns, ev.end_ns)
                        for ev in line.events]
    return []


def _name_gap(host, t):
    inner = None
    for name, s, e in host:
        if s <= t < e and (inner is None or e - s < inner[2] - inner[1]):
            inner = (name, s, e)
    return inner[0] if inner else "no host span"


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    host = _host_thread(planes)
    chips = []
    for plane in planes:
        if not _DEVICE.match(plane.name):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        ops = [(e.name, e.start_ns, e.end_ns) for e in lines["XLA Ops"]]
        busy = _union((s, e) for _, s, e in ops)
        modules: dict[str, list] = {}
        for e in lines.get("XLA Modules", []):
            m = modules.setdefault(e.name, [0, 0.0])
            m[0] += 1
            m[1] += e.duration_ns
        edges = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
        marks = [s for name, s, _ in host if name in HOST_MARKS]
        if marks and busy and min(marks) < busy[0][0]:
            edges.append((min(marks), busy[0][0]))
        gaps = [(b - a, _name_gap(host, (a + b) / 2)) for a, b in edges]
        chips.append({"busy_ns": float(sum(e - s for s, e in busy)),
                      "modules": modules, "ops": _self_times(ops),
                      "gaps": gaps})
    if not chips:
        raise ValueError(f"no device plane with XLA Ops in {path}")
    return {"chips": chips}


def reduce_dir(trace_dir: str, window_s: float = 0.0) -> dict:
    """All traced segments under ``trace_dir``, averaged over chips.

    Returns ``busy_s`` and ``window_s`` (the host-clock length of the
    traced segments, given by the harness), ``modules`` (name ->
    [executions, device seconds]), ``ops`` (instruction -> self seconds)
    and ``breakdown`` (the ten largest of each, gaps per chip).
    """
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise ValueError(f"no trace under {trace_dir}")
    busy, modules, ops, longest = 0.0, {}, {}, []
    n_chips = None
    for path in paths:
        chips = reduce_file(path)["chips"]
        n = len(chips)
        if n_chips not in (None, n):
            raise ValueError("traced segments disagree on the chip count")
        n_chips = n
        for chip in chips:
            busy += chip["busy_ns"] / n
            for name, (count, ns) in chip["modules"].items():
                m = modules.setdefault(name, [0.0, 0.0])
                m[0] += count / n
                m[1] += ns / n * 1e-9
            for name, ns in chip["ops"].items():
                ops[name] = ops.get(name, 0.0) + ns / n * 1e-9
            longest.extend((ns * 1e-9, name) for ns, name in chip["gaps"])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(longest, reverse=True)[:10]
    return {
        "busy_s": busy * 1e-9, "window_s": window_s, "chips": n_chips,
        "modules": modules, "ops": ops,
        "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                      "idle_gaps": [[name, s] for s, name in top_gaps]},
    }


def module_time(trace: dict, executions: float, prefix: str) -> float | None:
    """Device seconds of the one program whose name starts ``prefix`` and
    that ran exactly ``executions`` times (per chip), or None.  Programs of
    one name are told apart by their fingerprint, so this is how a reader
    finds, say, the decode step among the ``jit_step`` programs."""
    hits = [ns for name, (count, ns) in trace["modules"].items()
            if name.startswith(prefix) and abs(count - executions) < 1e-9]
    return hits[0] if len(hits) == 1 else None


def ops_time(trace: dict, prefix: str) -> float:
    """Self seconds of every instruction whose name starts ``prefix``."""
    return sum(v for k, v in trace["ops"].items() if k.startswith(prefix))
