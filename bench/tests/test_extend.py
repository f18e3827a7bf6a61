"""A new cell, metric, configuration and model family are files: added to a
copy of the benchmark, they are found and run by name with no other file
edited."""
import jax
import jax.numpy as jnp
import pytest

from bench import harness, hlo
from bench import trace as bt
from bench import train_common as tc
from bench.tests import cells

METRIC = '''"""``train_steps_traced``: steps the window ran."""


def read(art):
    return float(art["steps"]) if art.get("steps") else None
'''

# a second family: the program's dense GQA transformer, under Yi-6B's
# published config.json, with its reference
DENSE = ("families/dense.py", "configs/yi_6b.json", "configs/yi_6b_ref.py")
DENSE_CONFIG = {"name": "yi_6b", "source": "https://huggingface.co/01-ai/Yi-6B",
                "file": "bench/configs/yi_6b.json", "reduced": [],
                "why": "dense GQA with RoPE and SwiGLU: a second family"}


def existing_files():
    return {p.relative_to(harness.BENCH): p.read_bytes()
            for p in harness.BENCH.rglob("*")
            if p.is_file() and "tests" not in p.parts
            and "__pycache__" not in p.parts}


def assert_unchanged(before, bench):
    after = {p.relative_to(bench): p.read_bytes()
             for p in bench.rglob("*") if p.is_file()}
    assert all(after[p] == b for p, b in before.items())


def dense_cell(root, files=DENSE):
    base = harness.load_json(harness.BENCH / "workloads"
                             / "mamba2_370m.perfed_step.json")
    cell = dict(base, name="yi_6b.perfed_step", config="yi_6b")
    return cell, cells.add_cell(
        root, cell, traffic=base["traffic"], why="the dense family's step",
        reports=("train_tokens_per_s", "train_step_mfu", "step_flops_ratio"),
        files=files, config=DENSE_CONFIG)


def test_added_cell_and_metric_run_by_name(tmp_path):
    root = tmp_path / "checkout"
    before = existing_files()
    base = harness.load_json(harness.BENCH / "workloads"
                             / "mamba2_370m.perfed_step.json")
    cell = dict(base, name="mamba2_370m.perfed_copy", traffic="copy")
    bench = cells.add_cell(
        root, cell, traffic="copy", why="a copy under another name",
        reports=("train_tokens_per_s", "train_step_mfu"),
        metrics=[{"name": "train_steps_traced", "unit": "count",
                  "better": "higher", "source": "host_clock",
                  "layer": "SPMD step", "moves": "train_tokens_per_s",
                  "workloads": [cell["name"]]}])
    (bench / "metrics" / "train_steps_traced.py").write_text(METRIC)
    assert_unchanged(before, bench)

    found = cells.tiny(cell["name"], bench=bench)
    assert [m["name"] for m in found.per_layer] == [
        "train_step_mfu", "train_steps_traced"]
    out = found.driver.run(cells.ctx(found, tmp_path / "out"))
    assert cells.correct(out), out.checks
    assert out.e2e["train_tokens_per_s"] > 0
    reader = harness.metric_reader("train_steps_traced", bench=bench)
    assert reader({"steps": out.attempted}) == out.attempted


def dense_forward_dot_flops(fam, cfg, batch, seq):
    """Dot FLOPs per token of the program's own forward pass, compiled."""
    import dataclasses

    from repro.models import build_model

    model = build_model(dataclasses.replace(fam.program_config(cfg),
                                            remat=False))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    text = jax.jit(lambda p, t: model.forward(p, t)[0]).lower(
        params, tokens).compile().as_text()
    return hlo.analyze_hlo(text)["dot_flops_tc"] / (batch * seq)


def test_a_second_family_runs_through_the_train_driver(tmp_path,
                                                        monkeypatch):
    before = existing_files()
    cell, bench = dense_cell(tmp_path / "checkout")
    assert_unchanged(before, bench)

    found = cells.tiny(cell["name"], bench=bench)
    assert found.family.__file__ == str(bench / "families" / "dense.py")
    cfg, wl = found.config, found.workload
    f_tok = dense_forward_dot_flops(found.family, cfg, wl["batch"],
                                    wl["seq_len"])
    assert found.family.forward_per_token(cfg, wl["seq_len"]) == \
        pytest.approx(f_tok, rel=1e-9)

    # a traced run, with the profile a TPU would write stood in for by one
    # device busy for the whole window
    def load(trace_dir, chips):
        ms = 1_000_000
        return bt.Trace({
            "/host:CPU": {"python3": [("bench.window", 0, 1000 * ms)]},
            "/device:TPU:0": {"XLA Ops": [("fusion.1", 0, 1000 * ms)]}})
    monkeypatch.setattr(tc.btrace, "load", load)
    ctx = cells.ctx(found, tmp_path / "out")
    ctx.trace = True
    out = found.driver.run(ctx)
    assert cells.correct(out), out.checks
    assert out.e2e["train_tokens_per_s"] > 0

    # the model FLOPs are the dense family's, not another family's
    per_step = 15 * wl["batch"] * wl["seq_len"] * f_tok
    assert out.art["model_flops_step"] == pytest.approx(per_step, rel=1e-9)
    mfu = harness.metric_reader("train_step_mfu", bench=bench)(out.art)
    assert mfu == pytest.approx(
        100 * per_step * out.attempted / 197e12, rel=1e-9)
    ratio = harness.metric_reader("step_flops_ratio", bench=bench)(out.art)
    # the compiled step's dot FLOPs over the model's: recomputation under
    # remat puts it above 1
    assert 1.0 < ratio < 1.5


def test_a_config_naming_a_missing_family_fails_in_resolve(tmp_path):
    cell, bench = dense_cell(tmp_path / "checkout", files=DENSE[1:])
    with pytest.raises(FileNotFoundError, match=r"yi_6b\.json.*'dense'"):
        harness.resolve(cell["name"], bench=bench)
