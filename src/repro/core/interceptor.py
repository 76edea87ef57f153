"""Trace-time collective interception — the LD_PRELOAD analogue for JAX.

The paper's ComScribe preloads a shim over ``ncclAllReduce`` & friends so that
every collective an application issues is recorded without touching its
source.  A JAX application does not *call* a communication library at runtime;
it *traces* collective primitives (``psum``, ``all_gather``, ...) into a
program.  The faithful analogue is therefore a scoped hook on the ``bind`` of
every parallel primitive: while the :class:`CollectiveInterceptor` context is
active, any trace that executes — including inside ``jax.jit`` — logs a
:class:`~repro.core.events.TraceEvent` per collective, with primitive kind,
operand shapes/dtypes and mesh axes, then defers to the original bind.

This captures the *logical* (application-issued) communication.  The
*physical* schedule (what actually hits the wire, including compiler-inserted
resharding) comes from :mod:`repro.core.hlo_parser`; the monitor reports both
and their diff.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Callable, Optional

from jax._src.lax import parallel as _lax_parallel

from .events import TraceEvent, jax_shape

# primitive object name -> (logical primitive label, NCCL-style name)
_HOOKED_PRIMITIVES = {
    "psum_p": ("psum", "AllReduce"),
    "psum_invariant_p": ("psum", "AllReduce"),
    "unreduced_psum_p": ("psum", "AllReduce"),
    "pmax_p": ("pmax", "AllReduce"),
    "pmin_p": ("pmin", "AllReduce"),
    "all_gather_p": ("all_gather", "AllGather"),
    "all_gather_invariant_p": ("all_gather", "AllGather"),
    "reduce_scatter_p": ("psum_scatter", "ReduceScatter"),
    "unreduced_reduce_scatter_p": ("psum_scatter", "ReduceScatter"),
    "all_to_all_p": ("all_to_all", "AllToAll"),
    "ragged_all_to_all_p": ("ragged_all_to_all", "AllToAll"),
    "ppermute_p": ("ppermute", "SendRecv"),
    "pgather_p": ("pgather", "Gather"),
    "pbroadcast_p": ("pbroadcast", "Broadcast"),
}

_lock = threading.Lock()


def traced_summary(events) -> dict:
    """Paper Table-2 style logical summary over trace events.

    Module-level so multi-capture sessions (which accumulate events across
    many interceptor scopes) summarize exactly like a single interceptor.
    """
    table: dict[str, dict] = {}
    for ev in events:
        name = getattr(ev, "nccl_name", ev.primitive)
        row = table.setdefault(name, {"calls": 0, "payload_bytes": 0})
        row["calls"] += 1
        row["payload_bytes"] += ev.payload_bytes
    return table


def _axis_names(params: dict) -> tuple[str, ...]:
    ax = params.get("axes", params.get("axis_name", ()))
    if ax is None:
        ax = ()
    if isinstance(ax, (str, int)):
        ax = (ax,)
    return tuple(str(a) for a in ax)


class CollectiveInterceptor:
    """Scoped trace-time logger for JAX collective primitives.

    Usage::

        with CollectiveInterceptor(mesh=mesh) as icpt:
            jitted = jax.jit(step).lower(*args)    # trace happens here
        icpt.events   # -> list[TraceEvent]

    ``mesh`` (optional) resolves axis names to sizes so each event carries its
    group size.  Nested interceptors each observe every event (innermost
    first); hooks are reference-counted so nesting is safe.
    """

    def __init__(self, mesh=None, callback: Optional[Callable] = None):
        self.events: list[TraceEvent] = []
        self._axis_sizes: dict[str, int] = {}
        self._callback = callback
        if mesh is not None:
            self._axis_sizes = dict(
                zip(map(str, mesh.axis_names), mesh.devices.shape)
            )

    # -- book-keeping shared across (possibly nested) interceptors ---------
    _active: list["CollectiveInterceptor"] = []
    _originals: dict[str, Callable] = {}

    def __enter__(self):
        with _lock:
            if not CollectiveInterceptor._active:
                self._install()
            CollectiveInterceptor._active.append(self)
        return self

    def __exit__(self, *exc):
        with _lock:
            CollectiveInterceptor._active.remove(self)
            if not CollectiveInterceptor._active:
                self._uninstall()
        return False

    # -- hook plumbing ------------------------------------------------------
    @classmethod
    def _install(cls):
        for prim_name, (label, nccl) in _HOOKED_PRIMITIVES.items():
            prim = getattr(_lax_parallel, prim_name, None)
            if prim is None:  # tolerate jax version drift
                continue
            orig = prim.bind
            cls._originals[prim_name] = orig

            def make_hook(label=label, nccl=nccl, orig=orig):
                def hooked_bind(*args, **params):
                    for icpt in reversed(CollectiveInterceptor._active):
                        icpt._record(label, nccl, args, params)
                    return orig(*args, **params)

                return hooked_bind

            prim.bind = make_hook()

    @classmethod
    def _uninstall(cls):
        for prim_name, orig in cls._originals.items():
            prim = getattr(_lax_parallel, prim_name, None)
            if prim is not None:
                try:
                    del prim.bind  # remove instance attr, reveal class method
                except AttributeError:
                    prim.bind = orig
        cls._originals.clear()

    # -- event recording ----------------------------------------------------
    def _record(self, label: str, nccl: str, args, params):
        axes = _axis_names(params)
        size = 1
        known = True
        for a in axes:
            if a in self._axis_sizes:
                size *= self._axis_sizes[a]
            else:
                known = False
        shapes = []
        for a in args:
            if hasattr(a, "shape") and hasattr(a, "dtype"):
                shapes.append(jax_shape(a))
        ev = TraceEvent(
            primitive=label,
            axis_name=",".join(axes),
            arg_shapes=shapes,
            axis_size=size if known and axes else None,
        )
        ev.nccl_name = nccl  # annotate with the paper's primitive taxonomy
        self.events.append(ev)
        if self._callback is not None:
            self._callback(ev)

    # -- summaries (paper Table 2 style, logical view) -----------------------
    def summary(self) -> dict:
        return traced_summary(self.events)


@contextlib.contextmanager
def intercept(mesh=None):
    """Functional alias: ``with intercept(mesh) as icpt: ...``."""
    with CollectiveInterceptor(mesh=mesh) as icpt:
        yield icpt
