"""The benchmark's fixed arithmetic: peaks, model FLOPs, the HLO counter."""
import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import compare, harness, hlo
from bench import train_common as tc

BENCH = Path(harness.__file__).resolve().parent
mamba2 = harness.load_module(BENCH / "families" / "mamba2.py")


def test_peaks_refuse_an_unknown_device_kind():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        harness.peaks_for("TPU v9 imaginary")


def tiny_mamba(**kw):
    cfg = harness.load_json(BENCH / "configs" / "mamba2_370m.json")
    cfg.update(d_model=64, n_layer=2, vocab_size=256, d_state=16,
               headdim=16, chunk_size=32, dtype="float32", **kw)
    return cfg


@pytest.mark.parametrize("batch,seq", [(2, 128), (1, 256)])
def test_ssm_forward_flops_match_the_compiled_dot_count(batch, seq):
    from repro.models import build_model

    cfg = tiny_mamba()
    model = build_model(dataclasses.replace(mamba2.program_config(cfg),
                                            remat=False))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    compiled = jax.jit(lambda p, t: model.forward(p, t)[0]).lower(
        params, tokens).compile()
    counted = hlo.analyze_hlo(compiled.as_text())["dot_flops_tc"]
    assert counted == pytest.approx(
        batch * seq * mamba2.forward_per_token(cfg, seq), rel=1e-9)


def test_eq7_step_is_fifteen_forward_passes_per_role_token():
    cfg = tiny_mamba()
    assert tc.eq7_step(mamba2, cfg, 4, 2048) == 15 * 4 * 2048 * \
        mamba2.forward_per_token(cfg, 2048)


def test_full_size_mamba2_forward_flops():
    cfg = harness.load_json(BENCH / "configs" / "mamba2_370m.json")
    # 48 layers × (in_proj 2·1024·4384 + out_proj 2·2048·1024 + SSD
    # 2·256·128 + 2·256·32·64 + 4·32·64·128) + head 2·1024·50288 (the
    # 50,277 ids padded to a multiple of 16)
    per_layer = (2 * 1024 * 4384 + 2 * 2048 * 1024 + 2 * 256 * 128
                 + 2 * 256 * 32 * 64 + 4 * 32 * 64 * 128)
    assert mamba2.vocab_rows(cfg) == 50288
    assert mamba2.forward_per_token(cfg, 2048) == \
        48 * per_layer + 2 * 1024 * 50288


def test_major_gaps_leave_out_the_leaves_that_carry_no_update():
    ref = {"loss": [1.0], "grad_norm": [5.0],
           "grad_leaf": {"big": 0.99, "mid": 0.14, "small": 0.005},
           "change_first": {"big": 1.0, "mid": 0.1, "small": 0.01},
           "change_last": {"big": 2.0, "mid": 0.2, "small": 0.02}}
    assert compare.major(ref["grad_leaf"]) == ["big", "mid"]
    # the small leaf reads half its reference and the median leaf's change
    # is 0.1: it sets the worst leaf, not the major one
    prog = dict(ref, change_first={"big": 1.0, "mid": 0.1, "small": 0.06},
                change_last={"big": 2.0, "mid": 0.22, "small": 0.02})
    gaps = compare.train_gaps(prog, ref)
    assert gaps["grad_gap"] == pytest.approx(0.5)
    assert gaps["major_grad_gap"] == pytest.approx(0.0)
    assert gaps["major_change_gap"] == pytest.approx(0.1)
    assert gaps["change_gap"] == pytest.approx(0.1)
