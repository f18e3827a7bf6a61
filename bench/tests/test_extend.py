"""A new cell and a new per-layer metric are files: added to a copy of
the benchmark, they are found and run by name with no other file edited."""
from bench import harness
from bench.tests import cells

METRIC = '''"""``train_steps_traced``: steps the window ran."""


def read(art):
    return float(art["steps"]) if art.get("steps") else None
'''


def test_added_cell_and_metric_run_by_name(tmp_path):
    root = tmp_path / "checkout"
    before = {p.relative_to(harness.BENCH): p.read_bytes()
              for p in harness.BENCH.rglob("*")
              if p.is_file() and "tests" not in p.parts
              and "__pycache__" not in p.parts}
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

    after = {p.relative_to(bench): p.read_bytes()
             for p in bench.rglob("*") if p.is_file()}
    assert all(after[p] == b for p, b in before.items())

    found = cells.tiny(cell["name"], bench=bench)
    assert [m["name"] for m in found.per_layer] == [
        "train_step_mfu", "train_steps_traced"]
    out = found.driver.run(cells.ctx(found, tmp_path / "out"))
    assert cells.correct(out), out.checks
    assert out.e2e["train_tokens_per_s"] > 0
    reader = harness.metric_reader("train_steps_traced", bench=bench)
    assert reader({"steps": out.attempted}) == out.attempted
