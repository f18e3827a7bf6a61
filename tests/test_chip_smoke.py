"""``chip_smoke.py`` on the CPU: its phases at reduced size, its semi-sync
comparison on four virtual devices, and its refusal to report a result
when JAX finds no TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke
from repro.configs import get_config


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_a_platform_without_tpu(argv, capsys):
    rc = chip_smoke.main(argv)
    out = capsys.readouterr()
    assert rc != 0
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_simulator_phase_reduced():
    out = chip_smoke.simulator_phase(rounds=6, mobile_ues=32,
                                     mobile_rounds=2, data_n=600)
    static = out["static"]
    assert static["rounds"] == 6
    assert static["loss_last"] < static["loss_first"]
    eq8 = out["eq8_round"]
    assert eq8["backend"] == "jnp" and not eq8["tpu_custom_call"]
    assert eq8["max_abs_err_vs_jnp"] == 0.0
    assert eq8["update_max_abs"] > 0.0
    assert out["mobile"]["rounds"] == 2 and out["mobile"]["n_cells"] == 4


def test_train_phase_reduced():
    cfg = get_config("mamba2_370m").reduced()
    out = chip_smoke.train_phase(cfg, seq_len=64, batch=2, steps=2)
    assert [s["step"] for s in out["steps"]] == [0, 1]
    assert all(s["grad_norm"] > 0.0 for s in out["steps"])


SEMI_SYNC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
sys.path.insert(0, sys.argv[1])
import chip_smoke
from repro.configs import get_config

cfg = get_config("mamba2_370m").reduced()
cmp = chip_smoke.semi_sync_compare(cfg, cohorts=4, seq_len=32, per_cohort=2)
run = chip_smoke.semi_sync_run(cfg, cohorts=4, seq_len=32, per_cohort=2,
                               steps=2)
print(json.dumps({"compare": cmp, "run": run}))
"""


def test_semi_sync_across_four_devices_matches_one():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SEMI_SYNC_SCRIPT, str(ROOT)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["compare"]["devices"] == 4 and rec["compare"]["steps"] == 3
    assert [s["participants"] for s in rec["run"]["steps"]] == [4.0, 3.0]
