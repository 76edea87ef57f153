"""On-disk report cache: skip recompilation on repeated monitoring runs.

Compiling a model config is the hot path of iterative use -- seconds to
minutes per (config, mesh) cell -- while everything downstream (matrices,
tables, exports) derives from the parsed collective schedule in milliseconds.
So the sweep engine caches whole :class:`~repro.core.monitor.CommReport`
objects on disk, serialized through :mod:`repro.core.export.serialize`.

**Cache-key semantics.**  A key is the SHA-256 (first 20 hex chars) of the
JSON tuple ``(schema, config, mesh, algorithm, jax_version, device)``:

* ``config``  -- the sweep config identity *including its builder version
  string* (e.g. ``"gnmt/v1:d=64,layers=2,steps=4"``), so editing a builder
  invalidates its entries;
* ``mesh``    -- canonical mesh id, shape x axes (e.g. ``"4x2:data,model"``);
* ``algorithm`` -- collective algorithm used for byte/edge accounting
  (``ring`` / ``tree`` / ``hierarchical``); compilation does not depend on
  it, but the derived matrices and summaries do, so each algorithm gets its
  own entry (derivation from a sibling entry is still compile-free: a lazy
  ``CommReport.view(algorithm)`` binding, snapshotted by
  ``CommReport.rebound``);
* ``jax_version`` -- XLA's collective emission changes across releases, so
  reports never survive a jax upgrade;
* ``device``  -- the default backend's platform and ``device_kind`` (e.g.
  ``"cpu:cpu"``, ``"tpu:TPU v5 lite"``): the TPU compiler emits a different
  schedule than the CPU one, so a report compiled on forced host devices is
  never served as a chip's (the cache directory travels with the checkout).

**Phase-aware entries.**  Sessions capture under named phases, but a phase
is a *view* of the session snapshot, not a separate compilation -- so a
sweep cell keyed with ``phase=`` resolves to the SAME cache entry as the
whole session (:func:`cache_key` deliberately folds ``phase`` out of the
hash) and :meth:`ReportCache.get` hands back the cached whole-session
snapshot, from which ``report.view(phase=...)`` derives the per-phase
artifacts in milliseconds.  A phase the cached snapshot never captured is
a miss (the caller re-monitors the session, which then contains it).

The cache directory defaults to ``artifacts/report_cache`` (override with
``REPRO_CACHE_DIR`` or ``ReportCache(root=...)``).  Entries are one JSON file
per key, written atomically (tmp file + rename); a corrupt or unreadable
entry behaves as a miss.  Inspect or clear from the CLI::

    python -m repro cache            # list entries, total size
    python -m repro cache --clear
"""
from __future__ import annotations

import hashlib
import json
import os
import tempfile
from typing import Optional

_SCHEMA = "repro.report_cache.v1"
DEFAULT_ROOT = os.path.join("artifacts", "report_cache")


def device_id() -> str:
    """``"<platform>:<device_kind>"`` of the default backend's devices."""
    import jax
    dev = jax.devices()[0]
    return f"{dev.platform}:{dev.device_kind}"


def cache_key(config: str, mesh: str, algorithm: str,
              jax_version: Optional[str] = None, *,
              phase: Optional[str] = None,
              device: Optional[str] = None) -> str:
    """Deterministic key for one (config, mesh, algorithm, jax, device)
    cell; ``jax_version`` and ``device`` default to this process's.

    ``phase`` is accepted -- and deliberately **not hashed** -- so a
    per-phase sweep cell addresses the whole-session snapshot it derives
    from: ``cache_key(..., phase="decode") == cache_key(...)``.  Pass the
    phase to :meth:`ReportCache.get` instead to assert the cached snapshot
    actually captured it.
    """
    del phase  # key-neutral by design: phases are views of one snapshot
    if jax_version is None:
        import jax
        jax_version = jax.__version__
    if device is None:
        device = device_id()
    blob = json.dumps([_SCHEMA, config, mesh, algorithm, jax_version,
                       device])
    return hashlib.sha256(blob.encode()).hexdigest()[:20]


class ReportCache:
    """Directory of serialized CommReports, addressed by :func:`cache_key`."""

    def __init__(self, root: Optional[str] = None):
        self.root = root or os.environ.get("REPRO_CACHE_DIR") or DEFAULT_ROOT
        self.hits = 0
        self.misses = 0

    def path_for(self, key: str) -> str:
        return os.path.join(self.root, key + ".json")

    def get(self, key: str, phase: Optional[str] = None):
        """Cached CommReport for ``key``, or None (corrupt entry == miss).

        ``phase`` makes the lookup phase-aware: the WHOLE-session snapshot
        is returned (phases are lazy views over it -- derive with
        ``report.view(phase=...)``; nothing is recaptured), but a phase
        the snapshot never captured counts as a miss so the caller
        re-monitors a session that contains it.
        """
        path = self.path_for(key)
        try:
            with open(path) as f:
                payload = json.load(f)
            from .export import serialize
            report = serialize.report_from_dict(payload["report"])
        except (OSError, KeyError, ValueError, TypeError):
            self.misses += 1
            return None
        if phase is not None and phase not in report.phase_names():
            self.misses += 1
            return None
        report.meta = dict(payload.get("meta", {}))
        self.hits += 1
        return report

    def put(self, key: str, report, meta: Optional[dict] = None) -> str:
        """Store ``report`` under ``key`` atomically; returns the entry path."""
        from .export import serialize
        os.makedirs(self.root, exist_ok=True)
        payload = {
            "schema": _SCHEMA,
            "key": key,
            "meta": dict(meta or getattr(report, "meta", {}) or {}),
            "report": serialize.report_to_dict(report),
        }
        fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, self.path_for(key))
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return self.path_for(key)

    # ------------------------------------------------------------------
    # maintenance
    # ------------------------------------------------------------------
    def entries(self) -> list[dict]:
        out = []
        if not os.path.isdir(self.root):
            return out
        for fn in sorted(os.listdir(self.root)):
            if not fn.endswith(".json"):
                continue
            path = os.path.join(self.root, fn)
            entry = {"key": fn[:-5], "path": path,
                     "size": os.path.getsize(path)}
            try:
                with open(path) as f:
                    payload = json.load(f)
                entry["meta"] = payload.get("meta", {})
                entry["name"] = payload.get("report", {}).get("name", "?")
            except (OSError, ValueError, TypeError, AttributeError):
                entry["corrupt"] = True
            out.append(entry)
        return out

    def clear(self) -> int:
        """Delete every entry; returns the number removed."""
        n = 0
        for e in self.entries():
            os.unlink(e["path"])
            n += 1
        return n
