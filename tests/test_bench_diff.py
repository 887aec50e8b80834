"""Perf-regression sentinel (ISSUE 12): tools/bench_diff.py.

Exit-code contract: 0 = comparable + clean, 1 = regression, 2 =
refused (cross-backend / degraded / crash record), 3 = usage error.  Plus the
blackbox overlay mode of tools/trace_merge.py (who hung first).
"""

import importlib.util
import json
import os

import pytest

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"_{name}", os.path.join(_TOOLS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bd = _load("bench_diff")
tm = _load("trace_merge")


def _rec(**over):
    base = {"metric": "higgs1m_boosting_iters_per_sec", "value": 1.0,
            "train_auc": 0.81, "compile_s": 30.0, "n_programs": 10,
            "predict_rows_per_sec": 1e6, "serve_p99_ms": 5.0,
            "backend": "tpu", "degraded": False}
    base.update(over)
    return base


def _write(tmp_path, name, rec):
    p = tmp_path / name
    p.write_text(json.dumps(rec))
    return str(p)


class TestDiff:
    def test_clean_comparison_exits_zero(self, tmp_path):
        a = _write(tmp_path, "a.json", _rec())
        b = _write(tmp_path, "b.json", _rec(value=1.02))
        code, text = bd.run(old_path=a, new_path=b)
        assert code == bd.EXIT_OK
        assert "no regressions" in text

    def test_throughput_drop_is_a_regression(self, tmp_path):
        a = _write(tmp_path, "a.json", _rec())
        b = _write(tmp_path, "b.json", _rec(value=0.5))
        code, text = bd.run(old_path=a, new_path=b)
        assert code == bd.EXIT_REGRESSION
        assert "REGRESSION" in text and "value" in text

    def test_lower_better_direction(self, tmp_path):
        """compile_s GROWING is a regression; compile_s shrinking by
        the same ratio is an improvement, not a regression."""
        a = _write(tmp_path, "a.json", _rec())
        worse = _write(tmp_path, "w.json", _rec(compile_s=60.0))
        better = _write(tmp_path, "b.json", _rec(compile_s=15.0))
        assert bd.run(old_path=a, new_path=worse)[0] == \
            bd.EXIT_REGRESSION
        code, text = bd.run(old_path=a, new_path=better)
        assert code == bd.EXIT_OK and "improved" in text

    def test_within_tolerance_is_ok(self, tmp_path):
        a = _write(tmp_path, "a.json", _rec())
        b = _write(tmp_path, "b.json", _rec(value=0.9))  # -10% < 15% tol
        assert bd.run(old_path=a, new_path=b)[0] == bd.EXIT_OK

    def test_program_zoo_gate_is_exact(self, tmp_path):
        a = _write(tmp_path, "a.json", _rec())
        b = _write(tmp_path, "b.json", _rec(n_programs=11))
        code, text = bd.run(old_path=a, new_path=b)
        assert code == bd.EXIT_REGRESSION and "n_programs" in text

    def test_hbm_metrics_participate(self, tmp_path):
        a = _write(tmp_path, "a.json",
                   _rec(train_peak_hbm_bytes=1_000_000))
        b = _write(tmp_path, "b.json",
                   _rec(train_peak_hbm_bytes=2_000_000))
        code, text = bd.run(old_path=a, new_path=b)
        assert code == bd.EXIT_REGRESSION
        assert "train_peak_hbm_bytes" in text

    def test_zero_baseline_never_regresses(self, tmp_path):
        """A 0.0 baseline gives the relative tolerance no scale: a
        0.0 -> 0.01 serve_shed_pct move is noise, surfaced as
        new-nonzero, never a gate failure."""
        a = _write(tmp_path, "a.json", _rec(serve_shed_pct=0.0))
        b = _write(tmp_path, "b.json", _rec(serve_shed_pct=0.01))
        code, text = bd.run(old_path=a, new_path=b)
        assert code == bd.EXIT_OK and "new-nonzero" in text
        same = _write(tmp_path, "s.json", _rec(serve_shed_pct=0.0))
        assert bd.run(old_path=a, new_path=same)[0] == bd.EXIT_OK

    def test_null_metrics_are_skipped(self, tmp_path):
        """Explicit nulls (CPU rounds) drop out of the diff instead of
        crashing or comparing against numbers."""
        a = _write(tmp_path, "a.json", _rec(train_peak_hbm_bytes=None))
        b = _write(tmp_path, "b.json", _rec(train_peak_hbm_bytes=None))
        assert bd.run(old_path=a, new_path=b)[0] == bd.EXIT_OK


class TestRefusal:
    def test_cross_backend_refused_with_distinct_exit_code(self,
                                                           tmp_path):
        """The acceptance scenario: TPU-vs-degraded-CPU is refused
        loudly with an exit code DISTINCT from the regression one."""
        a = _write(tmp_path, "a.json", _rec(backend="tpu"))
        b = _write(tmp_path, "b.json",
                   _rec(backend="cpu", degraded=True, value=0.1))
        code, text = bd.run(old_path=a, new_path=b)
        assert code == bd.EXIT_REFUSED
        assert code != bd.EXIT_REGRESSION
        assert "REFUSED" in text and "cross-backend" in text

    def test_degraded_refused_by_default_allowed_explicitly(self,
                                                            tmp_path):
        a = _write(tmp_path, "a.json", _rec(backend="cpu",
                                            degraded=True))
        b = _write(tmp_path, "b.json", _rec(backend="cpu",
                                            degraded=True, value=1.01))
        code, text = bd.run(old_path=a, new_path=b)
        assert code == bd.EXIT_REFUSED and "degraded" in text
        code, text = bd.run(old_path=a, new_path=b, allow_degraded=True)
        assert code == bd.EXIT_OK

    def test_unreadable_record_is_a_usage_error_not_a_regression(
            self, tmp_path):
        """A missing/corrupt record must exit EXIT_ERROR (3), never the
        regression code 1 — CI treating them distinctly must not
        misreport a typo'd path as a perf regression."""
        a = _write(tmp_path, "a.json", _rec())
        code, text = bd.run(old_path=a,
                            new_path=str(tmp_path / "missing.json"))
        assert code == bd.EXIT_ERROR and "cannot read" in text
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert bd.run(old_path=a, new_path=str(bad))[0] == bd.EXIT_ERROR
        assert bd.main([a, str(bad)]) == bd.EXIT_ERROR

    def test_crash_record_refused(self, tmp_path):
        a = _write(tmp_path, "a.json", _rec())
        b = _write(tmp_path, "b.json",
                   _rec(value=0.0, error="RuntimeError: boom"))
        code, text = bd.run(old_path=a, new_path=b)
        assert code == bd.EXIT_REFUSED and "CRASH" in text

    def test_round_wrapper_records_refuse_when_degraded(self, tmp_path):
        """A driver round file wraps the bench line ({'rc', 'parsed'}):
        the wrapper is unwrapped, and two degraded cpu rounds refuse by
        default and diff under --allow-degraded."""
        deg = _rec(backend="cpu", degraded=True)
        a = _write(tmp_path, "r04.json", {"rc": 0, "parsed": deg})
        b = _write(tmp_path, "r05.json",
                   {"rc": 0, "parsed": {**deg, "value": 1.01}})
        code, text = bd.run(old_path=a, new_path=b)
        assert code == bd.EXIT_REFUSED and "degraded" in text
        code, text = bd.run(old_path=a, new_path=b, allow_degraded=True)
        assert code == bd.EXIT_OK          # within tolerance: clean

    def test_crashed_round_wrapper_refused(self, tmp_path):
        """A round that crashed before printing ({'rc': 1, 'parsed':
        null}) is a crash record, refused loudly — never an IO error and
        never silently skipped."""
        a = _write(tmp_path, "r01.json", {"rc": 1, "parsed": None})
        b = _write(tmp_path, "r02.json", {"rc": 0, "parsed": _rec()})
        code, text = bd.run(old_path=a, new_path=b)
        assert code == bd.EXIT_REFUSED and "CRASH" in text


class TestCLI:
    def test_main_exit_codes(self, tmp_path, capsys):
        a = _write(tmp_path, "a.json", _rec())
        b = _write(tmp_path, "b.json", _rec(value=0.4))
        assert bd.main([a, b]) == bd.EXIT_REGRESSION
        assert "REGRESSION" in capsys.readouterr().out
        assert bd.main(["--gate", a, b]) == bd.EXIT_REGRESSION
        ok = _write(tmp_path, "ok.json", _rec())
        assert bd.main([a, ok]) == bd.EXIT_OK

    def test_tolerance_scale(self, tmp_path):
        a = _write(tmp_path, "a.json", _rec())
        b = _write(tmp_path, "b.json", _rec(value=0.75))  # -25%
        assert bd.run(old_path=a, new_path=b)[0] == bd.EXIT_REGRESSION
        assert bd.run(old_path=a, new_path=b,
                      tolerance_scale=2.0)[0] == bd.EXIT_OK


# ---------------------------------------------------------------------------
# blackbox overlay (tools/trace_merge.py --blackbox)
# ---------------------------------------------------------------------------
class TestBlackboxOverlay:
    def _dump(self, tmp_path, host, entries, reason="collective_timeout"):
        rec = {"reason": reason, "host": host, "pid": 1, "t": 100.0,
               "ring_depth": 512, "entries": entries, "metrics": {}}
        (tmp_path / f"blackbox-host{host}.json").write_text(
            json.dumps(rec))

    def test_who_hung_first(self, tmp_path):
        """Host 0 entered its collective first and never left; host 1's
        later in-flight collective is it waiting on host 0 — the
        verdict must name host 0."""
        self._dump(tmp_path, 0, [
            {"t": 10.0, "kind": "span_begin", "name": "collective/eval",
             "tid": 1},
        ])
        self._dump(tmp_path, 1, [
            {"t": 9.0, "kind": "span_begin", "name": "collective/eval",
             "tid": 1},
            {"t": 9.5, "kind": "span_end", "name": "collective/eval",
             "tid": 1},
            {"t": 12.0, "kind": "span_begin",
             "name": "collective/checkpoint_barrier", "tid": 1},
        ])
        overlay, hosts, report = tm.merge_blackbox(str(tmp_path))
        assert hosts[0]["in_flight"]["name"] == "collective/eval"
        assert hosts[1]["in_flight"]["name"] == \
            "collective/checkpoint_barrier"
        verdict = report[-1]
        assert "host 0 hung first" in verdict
        assert "collective/eval" in verdict
        # overlay timeline is globally wall-clock ordered
        ts = [e["t"] for e in overlay["timeline"]]
        assert ts == sorted(ts)

    def test_no_hang_verdict(self, tmp_path):
        self._dump(tmp_path, 0, [
            {"t": 1.0, "kind": "span_begin", "name": "collective/x",
             "tid": 1},
            {"t": 2.0, "kind": "span_end", "name": "collective/x",
             "tid": 1},
        ], reason="guard_raise")
        _, hosts, report = tm.merge_blackbox(str(tmp_path))
        assert hosts[0]["in_flight"] is None
        assert "no in-flight collective" in report[-1]

    def test_missing_dir_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            tm.merge_blackbox(str(tmp_path))

    def test_cli_blackbox_mode(self, tmp_path, capsys):
        self._dump(tmp_path, 0, [
            {"t": 5.0, "kind": "span_begin", "name": "collective/sync",
             "tid": 1},
        ])
        out = tm.main([str(tmp_path), "--blackbox"])
        assert os.path.exists(out)
        assert "hung first" in capsys.readouterr().out
