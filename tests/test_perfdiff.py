"""Perf-regression harness (tools/perfdiff.py) over a ``BENCH_r*.json``
round history in the driver's wrapper shape — tier-1: every round must
stay parseable, the history walk must report the full MFU/throughput
trajectory, and an injected synthetic regression must exit nonzero.

The history is written by a fixture: the checked-in on-chip rounds were
records of a rig that no longer exists and left the tree in PR 21.

perfdiff is stdlib-only and loaded via importlib so the test exercises
exactly what ``python tools/perfdiff.py`` runs — no package import.
"""
import glob
import importlib.util
import json
import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_perfdiff():
    path = os.path.join(_ROOT, "tools", "perfdiff.py")
    spec = importlib.util.spec_from_file_location("_perfdiff", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def pd():
    return _load_perfdiff()


def _wrapped(n, metric, value, mfu=None, parsed=True):
    """One round as the driver records it: {n, cmd, rc, tail, parsed};
    ``tail`` is raw stdout (log noise, then the bench's one JSON line)."""
    raw = {"metric": metric, "value": value, "unit": "tokens/s",
           "vs_baseline": 0.0, "extra": {"device": "TPU v5 lite"}}
    if mfu is not None:
        raw["extra"]["mfu"] = mfu
    doc = {"n": n, "cmd": "python bench.py", "rc": 0,
           "tail": "WARNING: some backend noise\n" + json.dumps(raw) + "\n"}
    if parsed:
        doc["parsed"] = raw
    return doc


@pytest.fixture(scope="module")
def history(tmp_path_factory):
    """Six rounds: a first round on another config, four rounds of one
    training metric carrying MFU (one with only a ``tail`` to parse),
    and a CPU smoke round at the end."""
    d = tmp_path_factory.mktemp("rounds")
    train = "gpt3_1p3b_train_tokens_per_sec_chip"
    docs = [
        _wrapped(1, "gpt3_125m_train_tokens_per_sec_chip", 69000.0, 0.27),
        _wrapped(2, train, 9500.0, 0.40),
        _wrapped(3, train, 15200.0, 0.63, parsed=False),
        _wrapped(4, train, 15800.0, 0.66),
        _wrapped(5, train, 16000.0, 0.67),
        _wrapped(6, "gpt_tiny_train_tokens_per_sec_cpu_smoke", 11000.0),
    ]
    for doc in docs:
        (d / ("BENCH_r%02d.json" % doc["n"])).write_text(json.dumps(doc))
    return str(d / "BENCH_r*.json")


def _rounds(history):
    return sorted(glob.glob(history))


# ------------------------------------------------------------------ loading
class TestLoading:
    def test_all_rounds_parse(self, pd, history):
        paths = _rounds(history)
        assert len(paths) == 6
        for p in paths:
            doc = pd.load_doc(p)
            assert float(doc["value"]) > 0, p
            assert "metric" in doc, p
            assert doc["round"] >= 1, p

    def test_round_numbers_come_from_wrapper_then_filename(self, pd,
                                                           history,
                                                           tmp_path):
        doc = pd.load_doc(_rounds(history)[0])
        assert pd._round_of("whatever.json", doc) == doc["round"]
        p = tmp_path / "BENCH_r42.json"
        p.write_text(json.dumps({"metric": "m", "value": 1.0,
                                 "unit": "x"}))
        assert pd._round_of(str(p), pd.load_doc(str(p))) == 42

    def test_raw_and_tail_shapes(self, pd, tmp_path):
        raw = {"metric": "train.tokens_per_s", "value": 10.0,
               "unit": "tokens/s"}
        p1 = tmp_path / "raw.json"
        p1.write_text(json.dumps(raw))
        assert pd.load_doc(str(p1))["value"] == 10.0
        p2 = tmp_path / "wrapped.json"
        p2.write_text(json.dumps(
            {"n": 9, "rc": 0,
             "tail": "noise line\n" + json.dumps(raw) + "\n"}))
        doc = pd.load_doc(str(p2))
        assert doc["value"] == 10.0 and doc["round"] == 9

    def test_unusable_doc_raises(self, pd, tmp_path):
        p = tmp_path / "junk.json"
        p.write_text(json.dumps({"hello": "world"}))
        with pytest.raises(ValueError):
            pd.load_doc(str(p))


# ------------------------------------------------------------------ history
class TestHistory:
    def test_history_reports_full_trajectory(self, pd, history, capsys):
        rc = pd.run_history(history, noise=0.10, strict=False)
        out = capsys.readouterr().out
        # report-only: regressions in the past are printed, not fatal
        assert rc == 0
        n = len(_rounds(history))
        assert f"perfdiff history: {n} round(s)" in out
        for p in _rounds(history):
            doc = pd.load_doc(p)
            assert f"r{doc['round']:>04d}" in out
        assert "trajectory" in out
        # the recent rounds carry MFU -> the mfu trajectory line shows
        assert "mfu trajectory" in out

    def test_history_no_match_is_usage_error(self, pd, tmp_path):
        assert pd.run_history(str(tmp_path / "nope*.json"),
                              noise=0.10, strict=False) == 2


# --------------------------------------------------------------- diff mode
class TestDiff:
    def _write(self, tmp_path, name, value, mfu=None, att=None):
        doc = {"metric": "train.tokens_per_s", "value": value,
               "unit": "tokens/s", "extra": {}}
        if mfu is not None:
            doc["extra"]["mfu"] = mfu
        if att is not None:
            doc["extra"]["attribution"] = att
        p = tmp_path / name
        p.write_text(json.dumps(doc))
        return str(p)

    def test_synthetic_regression_exits_nonzero(self, pd, tmp_path):
        base = self._write(tmp_path, "base.json", 1000.0, mfu=0.40)
        # -30% throughput: beyond any sane noise bound
        bad = self._write(tmp_path, "bad.json", 700.0, mfu=0.40)
        assert pd.run_diff(base, bad, noise=0.10, mfu_noise=None,
                           attr_noise=0.10) == 1

    def test_within_noise_is_ok(self, pd, tmp_path):
        base = self._write(tmp_path, "base.json", 1000.0, mfu=0.40)
        ok = self._write(tmp_path, "ok.json", 950.0, mfu=0.39)
        assert pd.run_diff(base, ok, noise=0.10, mfu_noise=None,
                           attr_noise=0.10) == 0

    def test_mfu_only_regression_caught(self, pd, tmp_path):
        base = self._write(tmp_path, "base.json", 1000.0, mfu=0.40)
        bad = self._write(tmp_path, "bad.json", 1000.0, mfu=0.20)
        regs, _ = pd.compare(pd.load_doc(base), pd.load_doc(bad),
                             noise=0.10)
        assert any("mfu" in r for r in regs)

    def test_phase_fraction_growth_caught(self, pd, tmp_path):
        # throughput holds, but host_stall grows from 2% to 30% of the
        # step — exactly the regression tokens/s alone hides
        att_old = {"wall_ms": 100.0,
                   "segments_ms": {"device_compute": 98.0,
                                   "host_stall": 2.0}}
        att_new = {"wall_ms": 100.0,
                   "segments_ms": {"device_compute": 70.0,
                                   "host_stall": 30.0}}
        base = self._write(tmp_path, "base.json", 1000.0, att=att_old)
        bad = self._write(tmp_path, "bad.json", 1000.0, att=att_new)
        regs, _ = pd.compare(pd.load_doc(base), pd.load_doc(bad),
                             noise=0.10)
        assert any("host_stall" in r and "grew" in r for r in regs)

    def test_history_adjacent_diff_runs(self, pd, history):
        paths = _rounds(history)
        old = pd.load_doc(paths[-3])
        new = pd.load_doc(paths[-2])
        regs, notes = pd.compare(old, new, noise=0.10)
        # whatever the verdict, the comparison itself must be coherent
        assert isinstance(regs, list) and isinstance(notes, list)
        assert regs or notes


# ------------------------------------------------------ attribution checks
class TestAttributionInvariant:
    def test_valid_sum_passes(self, pd):
        att = {"wall_ms": 100.0,
               "segments_ms": {"data_wait": 1.0, "dispatch": 4.0,
                               "device_compute": 90.0,
                               "collective_exposed": 3.0,
                               "optimizer": 1.5, "host_stall": 0.5}}
        assert pd.check_attribution(att) == []

    def test_broken_sum_is_flagged(self, pd):
        att = {"wall_ms": 100.0,
               "segments_ms": {"device_compute": 80.0,
                               "host_stall": 0.5}}
        problems = pd.check_attribution(att)
        assert len(problems) == 1
        assert "invariant" in problems[0]

    def test_malformed_attribution_is_flagged(self, pd):
        assert pd.check_attribution("nope")
        assert pd.check_attribution({"wall_ms": 100.0})
        assert pd.check_attribution(
            {"wall_ms": 0.0, "segments_ms": {"a": 0.0}})
        assert pd.check_attribution(
            {"wall_ms": 10.0, "segments_ms": {"a": "NaNsense"}})

    def test_diff_fails_on_invariant_violation(self, pd, tmp_path):
        att = {"wall_ms": 100.0, "segments_ms": {"device_compute": 50.0}}
        doc = {"metric": "m", "value": 10.0, "unit": "x",
               "extra": {"attribution": att}}
        p = tmp_path / "broken.json"
        p.write_text(json.dumps(doc))
        regs, _ = pd.compare(pd.load_doc(str(p)), pd.load_doc(str(p)),
                             noise=0.10)
        # flagged on BOTH sides — a harness bug, not a perf delta
        assert sum("invariant" in r for r in regs) == 2


# ------------------------------------------------------------ bench wiring
class TestBenchWiring:
    def test_bench_exposes_maybe_perfdiff(self, pd, tmp_path,
                                          monkeypatch, capsys):
        import importlib.util as ilu

        spec = ilu.spec_from_file_location(
            "_bench_for_perfdiff", os.path.join(_ROOT, "bench.py"))
        bench = ilu.module_from_spec(spec)
        spec.loader.exec_module(bench)
        base = {"metric": "train.tokens_per_s", "value": 1000.0,
                "unit": "tokens/s"}
        bp = tmp_path / "base.json"
        bp.write_text(json.dumps(base))
        monkeypatch.setenv("PADDLE_TPU_PERFDIFF_BASE", str(bp))
        rc = bench._maybe_perfdiff({"metric": "train.tokens_per_s",
                                    "value": 500.0, "unit": "tokens/s"})
        assert rc == 1
        assert "REGRESSION" in capsys.readouterr().err
        rc = bench._maybe_perfdiff({"metric": "train.tokens_per_s",
                                    "value": 990.0, "unit": "tokens/s"})
        assert rc == 0
