"""Every cell, configuration and metric is a file found by its name."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

BENCH = Path(harness.__file__).resolve().parent
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_to_its_config_driver_and_metrics(cell):
    c = harness.resolve(cell)
    assert c.workload["name"] == cell
    assert callable(c.driver.run)
    assert c.config["name"] == c.workload["config"]
    assert {m["name"] for m in c.e2e} >= {"setup_s"}
    assert len(c.e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(harness.metric_reader(m["name"]))
        # a per-layer metric is reported where its end-to-end metric is
        assert m["moves"] in {e["name"] for e in c.e2e}
    limits = c.workload["limits"]
    assert limits and all(isinstance(v, (int, float)) for v in
                          limits.values())


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_every_config_names_its_family_and_cpu_sizes(config):
    """The family module builds, at the configuration's ``tiny`` sizes, the
    program whose parameters are the leaves the reference defines."""
    import jax

    from bench import train_common as tc
    from repro.models import build_model

    cfg = harness.load_json(BENCH / "configs" / f"{config}.json")
    fam = harness.load_module(BENCH / "families" / f"{cfg['family']}.py")
    ref = harness.load_module(BENCH / "configs" / f"{config}_ref.py")
    small = dict(cfg, **cfg["tiny"]["config"])
    seq = cfg["tiny"]["workload"]["seq_len"]
    assert fam.forward_per_token(small, seq) > 0
    model = build_model(fam.program_config(small))
    tc.check_layout(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                    ref.layout(small))


def test_workload_files_are_all_cells():
    files = {p.stem for p in (BENCH / "workloads").glob("*.json")}
    assert files == {w["name"] for w in SPEC["workloads"]}


def test_spec_follows_the_naming_and_shape_rules():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", [])) <= {w["name"] for w in
                                               SPEC["workloads"]}
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)


def test_run_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = SPEC["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
