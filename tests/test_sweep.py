"""Sweep engine: mesh parsing, registry, cache-backed no-recompile re-runs."""
import pytest

from repro import sweep
from repro.core import ReportCache


class TestMeshSpecs:
    def test_parse(self):
        assert sweep.parse_mesh("8") == ((8,), ("data",))
        assert sweep.parse_mesh("4x2") == ((4, 2), ("data", "model"))
        assert sweep.parse_mesh("2x2x2") == ((2, 2, 2),
                                             ("pod", "data", "model"))

    def test_mesh_id_canonical(self):
        assert sweep.mesh_id("4x2") == "4x2:data,model"

    def test_bad_spec(self):
        with pytest.raises(ValueError):
            sweep.parse_mesh("2x2x2x2")

    def test_mesh_larger_than_host_is_a_clean_error(self):
        """A mesh beyond the default backend's devices is refused before
        anything compiles, naming the count and the CPU-modelling route --
        never a silent switch to other devices."""
        with pytest.raises(ValueError, match=r"needs 16 devices .* has 8 "
                                             r"cpu .*JAX_PLATFORMS=cpu"):
            sweep.run_sweep(["paper"], ["4x2", "16"], ["ring"])

    def test_cli_mesh_larger_than_host_exits_2(self, capsys):
        from repro import cli
        rc = cli.main(["sweep", "--configs", "paper", "--meshes", "4x4",
                       "--no-cache"])
        assert rc == 2
        err = capsys.readouterr().err
        assert err.startswith("error: mesh 4x4 needs 16 devices")


class TestRegistry:
    def test_paper_apps_and_archs_present(self):
        from repro import configs
        names = set(sweep.available_configs())
        assert {"paper", "gnmt", "resnet"} <= names
        assert set(configs.ARCH_IDS) <= names

    def test_serve_config_is_multi_phase(self):
        """The serve config builds prefill/decode captures (a multi-phase
        session cell) instead of a single monitored function."""
        assert "serve" in sweep.available_configs()
        spec = sweep.available_configs()["serve"]
        assert "prefill/decode" in spec.description

    def test_moe_skew_config_present(self):
        assert "moe-skew" in sweep.available_configs()
        spec = sweep.available_configs()["moe-skew"]
        assert "irregular" in spec.description

    def test_unknown_config_rejected(self):
        with pytest.raises(KeyError):
            sweep.run_sweep(["nope"], ["4x2"], ["ring"])

    def test_unknown_algorithm_rejected(self):
        with pytest.raises(ValueError):
            sweep.run_sweep(["paper"], ["4x2"], ["nccl"])


class TestResolveJobs:
    def test_int_and_strings(self):
        assert sweep.resolve_jobs(1) == 1
        assert sweep.resolve_jobs(4) == 4
        assert sweep.resolve_jobs("2") == 2
        assert sweep.resolve_jobs(0) == 1          # floor at one worker

    def test_auto_is_cpu_count(self):
        import os
        assert sweep.resolve_jobs("auto") == max(1, os.cpu_count() or 1)
        assert sweep.resolve_jobs(" AUTO ") >= 1

    def test_garbage_rejected(self):
        with pytest.raises(ValueError):
            sweep.resolve_jobs("many")


class TestSweepRuns:
    pytestmark = pytest.mark.compile

    def test_cold_then_cached(self, tmp_path):
        cache = ReportCache(root=str(tmp_path / "cache"))
        logs: list[str] = []
        res = sweep.run_sweep(["paper"], ["4x2"], ["ring", "tree"],
                              cache=cache, log=logs.append)
        assert not res.failures
        assert res.compiles == 1              # tree derived, not recompiled
        assert [r.algorithm for r in res.reports] == ["ring", "tree"]
        assert any("derive" in l for l in logs)
        assert "paper" in res.summary_table()

        logs.clear()
        cache2 = ReportCache(root=str(tmp_path / "cache"))
        res2 = sweep.run_sweep(["paper"], ["4x2"], ["ring", "tree"],
                               cache=cache2, log=logs.append)
        assert res2.compiles == 0 and res2.cache_hits == 2
        assert all("[cache] hit" in l for l in logs)
        for a, b in zip(res.reports, res2.reports):
            assert a.matrix.sum() == pytest.approx(b.matrix.sum())

    def test_new_algorithm_derives_from_cached_sibling(self, tmp_path):
        cache = ReportCache(root=str(tmp_path / "cache"))
        sweep.run_sweep(["paper"], ["4x2"], ["ring"], cache=cache)
        logs: list[str] = []
        res = sweep.run_sweep(["paper"], ["4x2"], ["ring", "hierarchical"],
                              cache=ReportCache(root=str(tmp_path / "cache")),
                              log=logs.append)
        # the sibling ring entry satisfies hierarchical without compiling
        assert res.compiles == 0
        assert any("derive" in l and "hierarchical" in l for l in logs)

    def test_captures_build_monitors_one_session(self, mesh8):
        """A builder returning {"captures": ...} is monitored as ONE
        multi-phase session: phase-tagged ops, per-phase views, one
        snapshot per cell."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        ws = NamedSharding(mesh8, P(None, "model"))
        xs = NamedSharding(mesh8, P("data", None))
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        x = jax.ShapeDtypeStruct((32, 64), jnp.float32)

        def fwd(w, x):
            return ((x @ w) ** 2).mean()

        built = {"captures": [
            {"phase": "prefill", "name": "fwd", "fn": fwd, "args": (w, x),
             "kwargs": {"in_shardings": (ws, xs)}},
            {"phase": "decode", "name": "bwd",
             "fn": jax.value_and_grad(fwd), "args": (w, x),
             "kwargs": {"in_shardings": (ws, xs)}},
        ]}
        rep = sweep._monitor_cell(built, mesh8, "serve@4x2", "ring")
        assert rep.phase_names() == ["prefill", "decode"]
        assert {op.phase for op in rep.compiled_ops} <= \
            {"prefill", "decode"}
        res = sweep.SweepResult(reports=[rep], failures=[], cache_hits=0,
                                compiles=1)
        table = res.summary_table(by_phase=True)
        assert "prefill" in table and "decode" in table

    def test_phase_keyed_cell_reuses_session_snapshot(self, tmp_path,
                                                      mesh8):
        """Satellite: a sweep cell keyed with phase= hits the cached
        whole-session snapshot instead of recapturing."""
        from repro.core import ReportCache, cache_key
        import jax
        import jax.numpy as jnp

        built = {"captures": [
            {"phase": "prefill", "fn": lambda x: x.sum(),
             "args": (jax.ShapeDtypeStruct((8, 8), jnp.float32),)},
        ]}
        rep = sweep._monitor_cell(built, mesh8, "serve@4x2", "ring")
        cache = ReportCache(root=str(tmp_path / "cache"))
        key = cache_key("serve/v1", "4x2:data,model", "ring")
        cache.put(key, rep)
        hit = cache.get(cache_key("serve/v1", "4x2:data,model", "ring",
                                  phase="prefill"), phase="prefill")
        assert hit is not None and hit.phase_names() == ["prefill"]
        assert cache.get(key, phase="decode") is None   # never captured

    def test_moe_skew_cell_carries_irregular_vectors(self, mesh8):
        """The moe-skew builder's ``op_transform`` hook threads through
        ``_monitor_cell``: every captured a2a carries a per-rank byte
        vector with the hot expert above the skewed-a2a threshold, the
        summary grows the ``max_skew`` column, and the lint pass fires."""
        built = sweep.available_configs()["moe-skew"].build(mesh8)
        assert callable(built.get("op_transform"))
        rep = sweep._monitor_cell(built, mesh8, "moe-skew@4x2", "ring")
        a2as = [op for op in rep.compiled_ops
                if op.kind in ("all-to-all", "ragged-all-to-all")]
        assert a2as
        for op in a2as:
            vec = op.byte_vector()
            assert vec is not None
            assert vec.sum() == pytest.approx(op.payload_bytes)
            assert op.skew() > 2.0
        assert any(row.get("max_skew", 1.0) > 2.0
                   for row in rep.compiled_summary.values())
        assert any(f.rule_id == "skewed-a2a" for f in rep.lint())

    def test_parallel_and_serial_sweeps_are_identical(self, tmp_path):
        """``--jobs N`` must be invisible in the output: same report
        order, same counters, byte-identical summary CSV and table."""
        from repro.core.export import csv_exporter

        serial = sweep.run_sweep(
            ["paper"], ["4x2", "8"], ["ring", "tree"],
            cache=ReportCache(root=str(tmp_path / "c1")),
            log=lambda _: None)
        par = sweep.run_sweep(
            ["paper"], ["4x2", "8"], ["ring", "tree"],
            cache=ReportCache(root=str(tmp_path / "c2")), jobs=3,
            log=lambda _: None)
        assert not serial.failures and not par.failures
        assert serial.compiles == par.compiles == 2
        assert [(r.meta["config"], r.meta["mesh"], r.algorithm)
                for r in serial.reports] == \
               [(r.meta["config"], r.meta["mesh"], r.algorithm)
                for r in par.reports]
        p1 = csv_exporter.export_summary_csv(
            serial.reports, str(tmp_path / "serial.csv"))
        p2 = csv_exporter.export_summary_csv(
            par.reports, str(tmp_path / "parallel.csv"))
        with open(p1) as f1, open(p2) as f2:
            assert f1.read() == f2.read()
        assert serial.summary_table() == par.summary_table()

    def test_unrequested_sibling_spares_compile(self, tmp_path):
        cache = ReportCache(root=str(tmp_path / "cache"))
        sweep.run_sweep(["paper"], ["4x2"], ["ring"], cache=cache)
        logs: list[str] = []
        res = sweep.run_sweep(["paper"], ["4x2"], ["tree"],
                              cache=ReportCache(root=str(tmp_path / "cache")),
                              log=logs.append)
        # ring wasn't requested this time, but its cache entry still spares
        # the compile: tree derives from it
        assert res.compiles == 0
        assert any("sibling hit" in l for l in logs)
        assert res.reports[0].algorithm == "tree"
