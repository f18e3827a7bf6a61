"""Telemetry layer: no-op fast path, span accounting, read-only tracing
(golden trajectories bitwise unchanged with tracing ON), JSONL schema
round-trip + invariants, trace_report rendering, and reporter levels.
"""
import dataclasses
import io
import time

import numpy as np
import pytest

from repro.config import ExperimentConfig, FLConfig, MobilityConfig
from repro.configs import get_config
from repro.data import partition_noniid, synthetic_mnist
from repro.fl.simulation import run_simulation
from repro.models import build_model
from repro.obs import (NOOP, NoopTracer, Reporter, Tracer, current, use,
                       validate_rows)
from repro.obs import trace as obs_trace
from repro.obs.recorder import (REQUIRED_KEYS, SCHEMA, split_rows,
                                staleness_histogram)
from repro.utils.metrics import read_metrics

_DATA = synthetic_mnist(n=600, seed=21)
_MODEL = build_model(get_config("mnist_dnn"))


def _cfg(n=8, a=3, s=3, **fl_kw):
    return ExperimentConfig(
        model=get_config("mnist_dnn"),
        fl=FLConfig(n_ues=n, participants_per_round=a, staleness_bound=s,
                    alpha=0.03, beta=0.07, inner_batch=8, outer_batch=8,
                    hessian_batch=8, **fl_kw))


def _clients(n=8, seed=0):
    return partition_noniid(_DATA, n, n_labels=4, seed=seed)


def _mobile_cfg(n=24, **mob_kw):
    kw = dict(enabled=True, model="random_waypoint", speed_mps=30.0,
              n_cells=3, hierarchy=True, cloud_sync_every=4, step_s=0.2)
    kw.update(mob_kw)
    return dataclasses.replace(
        _cfg(n=n, a=max(1, n // 8), s=4, first_order=True),
        mobility=MobilityConfig(**kw))


# ---------------------------------------------------------------------------
# disabled fast path
# ---------------------------------------------------------------------------

def test_noop_is_the_default_current_tracer():
    assert current() is NOOP
    assert obs_trace.CURRENT is NOOP
    assert NOOP.enabled is False and NOOP.device_timing is False


def test_noop_span_is_one_shared_object():
    a = NOOP.span("x")
    b = NOOP.span("y")
    assert a is b                      # no allocation per call site
    with a:
        pass
    assert NOOP.add("c") is None
    assert NOOP.device_call("d", lambda v: v + 1, 41) == 42
    snap = NOOP.snapshot()
    assert snap == {"phase_s": {}, "counts": {}, "device_s": 0.0,
                    "device_phase_s": {}}


def test_use_installs_and_restores_current():
    tr = Tracer()
    with use(tr) as installed:
        assert installed is tr and current() is tr
        with use(None):                # nested None → NOOP
            assert current() is NOOP
        assert current() is tr
    assert current() is NOOP


def test_noop_call_site_cost_is_sub_microsecond():
    # the hot-loop contract: a disabled call site is one attribute fetch
    # plus an empty method call — budget is generous (5 µs/op) so shared
    # CI boxes can't flake, while a regression to real timing syscalls
    # per call (≈ the no-op cost ×50) still fails loudly
    n = 200_000
    tr = obs_trace.CURRENT
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("x"):
            pass
    dt = time.perf_counter() - t0
    assert dt / n < 5e-6, f"no-op span costs {dt/n*1e9:.0f}ns"


# ---------------------------------------------------------------------------
# live tracer accounting
# ---------------------------------------------------------------------------

def test_span_exclusive_time_nesting():
    tr = Tracer()
    with tr.span("outer"):
        time.sleep(0.02)
        with tr.span("inner"):
            time.sleep(0.03)
    assert tr.phase_s["inner"] >= 0.025
    # outer's exclusive time excludes inner's
    assert tr.phase_s["outer"] < 0.03
    assert tr.phase_s["outer"] >= 0.01


def test_counters_accumulate():
    tr = Tracer()
    tr.add("a")
    tr.add("a", 4)
    tr.add("b", 2)
    assert tr.counts == {"a": 5, "b": 2}


def test_device_call_attribution_and_reentrancy():
    tr = Tracer(device=True)

    def inner():
        return tr.device_call("inner", lambda: np.float64(1.0))

    out = tr.device_call("outer", inner)
    assert float(out) == 1.0
    # only the outermost frame accumulated
    assert "outer" in tr.device_phase_s
    assert "inner" not in tr.device_phase_s
    # spans opened inside a device frame are no-ops (no double-booking)
    def spanning():
        with tr.span("nested_host"):
            return 7
    assert tr.device_call("outer", spanning) == 7
    assert "nested_host" not in tr.phase_s


def test_device_timing_off_never_blocks_or_books():
    tr = Tracer(device=False)
    assert tr.device_call("x", lambda: 3) == 3
    assert tr.device_s == 0.0 and tr.device_phase_s == {}


def test_profile_trace_raises_when_the_profiler_cannot_start(tmp_path):
    """A run that asked for a trace never ends without one: a profiler
    that cannot start (here: one is already running) raises."""
    with obs_trace.profile_trace(str(tmp_path / "outer")):
        with pytest.raises(RuntimeError, match="already been started"):
            with obs_trace.profile_trace(str(tmp_path / "inner")):
                pass
    assert list((tmp_path / "outer").rglob("*.xplane.pb"))
    with obs_trace.profile_trace(None):   # no logdir: a no-op
        pass


def test_staleness_histogram_clips_and_folds():
    h = staleness_histogram(np.array([0, 1, 1, 99, -5]), cap=4)
    assert h == [2, 2, 0, 0, 1] and sum(h) == 5


# ---------------------------------------------------------------------------
# read-only contract: goldens bitwise unchanged with tracing fully ON
# ---------------------------------------------------------------------------

def test_static_golden_trajectory_with_tracing_enabled(tmp_path):
    """The pre-refactor golden of test_driver.py, run with device-timing
    tracing AND JSONL recording enabled — bitwise identical times/Π."""
    tr = Tracer(device=True)
    res = run_simulation(_cfg(), _MODEL, _clients(), algorithm="perfed",
                         mode="semi", max_rounds=6, eval_every=2, seed=0,
                         tracer=tr, trace_dir=str(tmp_path))
    assert [float(t).hex() for t in res.times] == [
        "0x0.0p+0", "0x1.b877293c2d615p-1",
        "0x1.ae97a23acc733p+0", "0x1.4066315c4298cp+1"]
    assert float(res.total_time).hex() == "0x1.4066315c4298cp+1"
    assert res.pi.tolist() == [
        [1, 0, 0, 1, 0, 0, 0, 1], [0, 0, 1, 0, 0, 1, 1, 0],
        [0, 1, 0, 0, 1, 0, 0, 1], [1, 0, 1, 1, 0, 0, 0, 0],
        [0, 0, 0, 0, 0, 1, 1, 1], [0, 1, 1, 0, 1, 0, 0, 0]]
    assert res.payload_dispatches == 8
    assert res.payloads_computed == 18
    np.testing.assert_allclose(res.losses, [
        2.3583488166332245, 1.8240666687488556,
        1.4705257415771484, 1.1463348343968391], rtol=1e-6)
    # telemetry attached and coherent
    t = res.telemetry
    assert t is not None and t["schema"] == SCHEMA
    assert t["rounds"] == 6 and t["arrivals"] == 18
    assert t["counts"]["driver.rounds_fused"] == 6


def test_mobile_traced_equals_untraced_bitwise(tmp_path):
    """Mobile multi-cell hierarchy run: tracing must not perturb the
    trajectory (fresh clients per run — their samplers carry RNG state)."""
    cfg = _mobile_cfg()
    kw = dict(algorithm="perfed", mode="semi", bandwidth_policy="equal",
              max_rounds=5, eval_every=2, seed=0)
    r0 = run_simulation(cfg, _MODEL, _clients(24, seed=1), **kw)
    r1 = run_simulation(cfg, _MODEL, _clients(24, seed=1),
                        tracer=Tracer(device=True),
                        trace_dir=str(tmp_path), **kw)
    assert np.array_equal(r0.times, r1.times)
    assert np.array_equal(r0.losses, r1.losses)
    assert np.array_equal(r0.pi, r1.pi)
    assert r0.handovers == r1.handovers
    assert r0.payload_dispatches == r1.payload_dispatches
    assert r1.telemetry is not None
    assert r0.telemetry is None        # untraced → no telemetry


# ---------------------------------------------------------------------------
# JSONL schema round-trip + per-round invariants
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    td = tmp_path_factory.mktemp("trace")
    tr = Tracer(device=True)
    res = run_simulation(_mobile_cfg(), _MODEL, _clients(24, seed=1),
                         algorithm="perfed", mode="semi",
                         bandwidth_policy="equal", max_rounds=5,
                         eval_every=2, seed=0, tracer=tr,
                         trace_dir=str(td))
    return res, read_metrics(res.telemetry["trace_path"])


def test_trace_jsonl_schema_roundtrip(traced_run):
    res, rows = traced_run
    meta, recs, summary = split_rows(rows)
    assert meta["schema"] == SCHEMA and meta["n_ues"] == 24
    assert len(recs) == res.telemetry["rounds"] == 5
    for r in recs:
        for k in REQUIRED_KEYS:
            assert k in r
    assert summary["arrivals"] == sum(r["a"] for r in recs)
    assert validate_rows(rows) == []


def test_trace_per_round_invariants(traced_run):
    res, rows = traced_run
    _, recs, summary = split_rows(rows)
    for r in recs:
        # phase seconds (exclusive) can never exceed the round's wall
        assert sum(r["phase_s"].values()) <= r["wall_s"] * 1.05 + 1e-6
        assert r["device_s"] <= r["wall_s"] * 1.05 + 1e-6
        # A_c equals the arrived-UE set consumed by that round
        assert r["a"] == len(r["ues"]) >= 1
        assert sum(r["staleness_hist"]) >= r["a"]
    # summary totals match SimResult counters
    assert summary["handovers"] == res.handovers
    assert summary["cloud_rounds"] == res.cloud_rounds
    per_cell = {int(c): a for c, a in summary["per_cell_a"].items()}
    assert sum(per_cell.values()) == summary["arrivals"]


def test_validate_rows_catches_corruption(traced_run):
    _, rows = traced_run
    import copy
    bad = copy.deepcopy(rows)
    del bad[0]["_meta"]["schema"]
    assert any("schema" in e for e in validate_rows(bad))
    bad = copy.deepcopy(rows)
    bad[1]["a"] = bad[1]["a"] + 1
    assert any("inconsistent" in e for e in validate_rows(bad))
    bad = copy.deepcopy(rows)
    bad[1]["phase_s"] = {"drain": bad[1]["wall_s"] * 10}
    assert any("exceed" in e for e in validate_rows(bad))
    assert validate_rows([]) != []


def test_trace_report_renders_and_checks(traced_run, capsys):
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    try:
        from trace_report import main, render
    finally:
        sys.path.pop(0)
    res, rows = traced_run
    text = render(rows)
    assert "phase breakdown" in text and "rounds=5" in text
    assert main([res.telemetry["trace_path"], "--check"]) == 0
    out = capsys.readouterr().out
    assert "OK" in out


# ---------------------------------------------------------------------------
# config plumbing + reporter
# ---------------------------------------------------------------------------

def test_cfg_obs_enables_tracing(tmp_path):
    cfg = dataclasses.replace(
        _cfg(), obs=dataclasses.replace(
            _cfg().obs, trace=True, trace_dir=str(tmp_path)))
    res = run_simulation(cfg, _MODEL, _clients(), max_rounds=3,
                         eval_every=0, seed=0)
    assert res.telemetry is not None
    assert validate_rows(read_metrics(res.telemetry["trace_path"])) == []


def test_reporter_levels_and_verbose_compat():
    out = io.StringIO()
    rep = Reporter("quiet", stream=out)
    rep.progress("p")
    rep.debug("d")
    assert out.getvalue() == ""
    out = io.StringIO()
    rep = Reporter("progress", stream=out)
    rep.progress("p")
    rep.debug("d")
    assert out.getvalue() == "p\n"
    out = io.StringIO()
    rep = Reporter("debug", stream=out)
    rep.progress("p")
    rep.debug("d")
    assert out.getvalue() == "p\nd\n"
    with pytest.raises(ValueError):
        Reporter("loud")


def test_verbose_progress_line_format_unchanged(capsys):
    """verbose=True must keep emitting the exact pre-telemetry line."""
    run_simulation(_cfg(), _MODEL, _clients(), algorithm="perfed",
                   mode="semi", max_rounds=2, eval_every=2, seed=0,
                   verbose=True)
    out = capsys.readouterr().out
    assert "[perfed-semi] round    2 t=" in out
    assert "ploss=" in out and "gloss=" in out


def test_noop_tracer_type_importable():
    assert isinstance(NOOP, NoopTracer)
