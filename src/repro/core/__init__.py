# CommScribe-JAX core: the paper's contribution (collective-communication
# monitoring) as a composable library. See DESIGN.md §3.
from .events import (CollectiveOp, HostTransfer, PhaseRecord, Shape,
                     TraceEvent, jax_shape)
from .interceptor import CollectiveInterceptor, intercept, traced_summary
from .hlo_parser import parse_hlo_collectives, summarize, total_wire_bytes
from .comm_matrix import (LinkUtilization, add_host_transfers,
                          link_utilization_for_ops, matrix_for_ops,
                          per_primitive_matrices, project_links)
# NOTE: the decompose() function itself is NOT re-exported at package
# level -- binding the name here would shadow the repro.core.decompose
# submodule attribute (import it via `from repro.core.decompose import
# decompose`); only the IR types and the warning are lifted.
from .decompose import (CollectiveSchedule, CommPhase,
                        HierarchicalFallbackWarning)
from .cost_models import (ALGORITHMS, collective_time, contention_time,
                          device_send_bytes, table1_allreduce_bytes,
                          validate_algorithm, wire_bytes_per_rank)
from .sparse import (SPARSE_DEVICE_THRESHOLD, SparseCommMatrix, from_dense,
                     is_sparse)
from .topology import HardwareSpec, Link, MeshTopology, V5E
from .views import CommView
from .monitor import CommReport, monitor_fn, roofline_of
from .session import Capture, MonitorSession
from .roofline import RooflineReport, analyze as roofline_analyze
from .report_cache import ReportCache, cache_key
from . import reporter
from . import export
from . import trace
from .trace import (CompareResult, TraceImport, TraceParseError, load_trace,
                    trace_compare)

__all__ = [
    "trace", "TraceImport", "TraceParseError", "load_trace",
    "CompareResult", "trace_compare",
    "CollectiveOp", "HostTransfer", "PhaseRecord", "Shape", "TraceEvent",
    "jax_shape",
    "CollectiveInterceptor", "intercept", "traced_summary",
    "parse_hlo_collectives", "summarize", "total_wire_bytes",
    "matrix_for_ops", "per_primitive_matrices", "add_host_transfers",
    "LinkUtilization", "project_links", "link_utilization_for_ops",
    "CollectiveSchedule", "CommPhase", "HierarchicalFallbackWarning",
    "ALGORITHMS", "validate_algorithm",
    "wire_bytes_per_rank", "collective_time", "table1_allreduce_bytes",
    "contention_time", "device_send_bytes",
    "SPARSE_DEVICE_THRESHOLD", "SparseCommMatrix", "from_dense", "is_sparse",
    "HardwareSpec", "Link", "MeshTopology", "V5E",
    "CommView", "CommReport", "monitor_fn", "roofline_of",
    "Capture", "MonitorSession",
    "RooflineReport", "roofline_analyze",
    "ReportCache", "cache_key",
    "reporter", "export",
]
