"""The split of the step's device time and dot FLOPs by named scope."""
import contextlib
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import harness, hlo, scopes
from bench import trace as bt
from bench.tests import cells

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000
CELL = "mamba2_370m.perfed_step"
METRICS = {"eq7_adapt_ms": ("phases", "perfed.adapt"),
           "eq7_outer_ms": ("phases", "perfed.outer"),
           "eq7_hvp_ms": ("phases", "perfed.hvp"),
           "eq7_update_ms": ("phases", "train.update"),
           "unscoped_ms": ("phases", "unscoped"),
           "remat_ms": ("parts", "remat"),
           "ssd_scan_ms": ("parts", "ssm.ssd")}


@pytest.mark.parametrize("path,names", [
    ("jit(step_fn)/perfed.adapt/jvp()/while/body/mul",
     ["step_fn", "perfed.adapt", "", "while", "body", "mul"]),
    ("jit(step_fn)/perfed.hvp/jvp(transpose(jvp(ssm.head)))/mul",
     ["step_fn", "perfed.hvp", "ssm.head", "mul"]),
    ("transpose(jvp(perfed.outer/ssm.ssd))/add",
     ["perfed.outer", "ssm.ssd", "add"]),
    ("", [""]),
])
def test_components_unwrap_transforms(path, names):
    assert scopes.components(path) == names


@pytest.mark.parametrize("path,scope", [
    ("jit(step_fn)/perfed.hvp/jvp(transpose(jvp()))/while/body/closed_call/"
     "checkpoint/rematted_computation/ssm.ssd/mul",
     scopes.Scope("perfed.hvp", "ssm.ssd", True)),
    # the loss's adaptation nests perfed.adapt inside perfed.loss
    ("jit(step_fn)/perfed.loss/perfed.adapt/transpose(jvp())/ssm.conv/add",
     scopes.Scope("perfed.loss", "ssm.conv", False)),
    ("jit(step_fn)/transpose(jvp(perfed.outer))/ssm.head/dot_general",
     scopes.Scope("perfed.outer", "ssm.head", False)),
    # a phase's name must be a whole component; a dotted name that is no
    # phase is a part
    ("jit(step_fn)/perfed.adapter/mul",
     scopes.Scope(scopes.UNSCOPED, "perfed.adapter", False)),
    ("copy", scopes.NONE),
    # the parts of other families: the first scope named with a dot
    ("jit(step_fn)/perfed.outer/transpose(jvp(attn.core))/dot_general",
     scopes.Scope("perfed.outer", "attn.core", False)),
    ("jit(step_fn)/perfed.hvp/while/body/checkpoint/rematted_computation/"
     "moe.experts/dot_general", scopes.Scope("perfed.hvp", "moe.experts",
                                             True)),
    ("jit(step_fn)/perfed.adapt/hybrid.layer/jvp(ssm.ssd)/mul",
     scopes.Scope("perfed.adapt", "hybrid.layer", False)),
])
def test_classify_takes_the_first_phase(path, scope):
    assert scopes.classify(path) == scope


# ---------------------------------------------------------------------------
# a hand-written module and trace
# ---------------------------------------------------------------------------

HVP = "jit(step_fn)/perfed.hvp/jvp(transpose(jvp()))/while"
IN_PROJ = (HVP + "/body/closed_call/checkpoint/rematted_computation/"
           "ssm.in_proj/dot_general")
SSD = HVP + "/body/while/body/ssm.ssd/dot_general"
LOSS = "jit(step_fn)/perfed.loss/perfed.adapt/ssm.out_proj/dot_general"
UPDATE = "jit(step_fn)/train.update/mul"
OUTER = "jit(step_fn)/transpose(jvp(perfed.outer))/ssm.ssd/add"
F8 = "f32[8,8]{1,0}"
LOOP = "(s32[], f32[8,8]{1,0})"


def meta(op_name):
    return f'metadata={{op_name="{op_name}"}}'


def fused(name, root):
    return [f"%{name} (p: f32[8,8]) -> f32[8,8] {{",
            f"  %p = {F8} parameter(0)", f"  ROOT {root}", "}", ""]


def fusion(name, operand, calls, op_name, root=False):
    return (f"  {'ROOT ' if root else ''}%{name} = {F8} fusion(%{operand}), "
            f"kind=kOutput, calls=%{calls}, {meta(op_name)}")


def loop(cond, body, bound, lines):
    return ([f"%{cond} (t: {LOOP}) -> pred[] {{",
             f"  %t = {LOOP} parameter(0)",
             "  %i = s32[] get-tuple-element(%t), index=0",
             f"  %bound.{cond} = s32[] constant({bound})",
             f"  ROOT %lt.{cond} = pred[] compare(%i, %bound.{cond}), "
             "direction=LT", "}", "",
             f"%{body} (t: {LOOP}) -> {LOOP} {{",
             f"  %t = {LOOP} parameter(0)"] + lines + ["}", ""])


def dot(name, op_name):
    return (f"%{name} = {F8} dot(%p, %p), lhs_contracting_dims={{1}}, "
            f"rhs_contracting_dims={{0}}, {meta(op_name)}")


MODULE = "\n".join(
    ["HloModule jit_step_fn, is_scheduled=true", ""]
    + fused("fused_in_proj", dot("dot.1", IN_PROJ))
    + fused("fused_ssd", dot("dot.2", SSD))
    + fused("fused_loss", dot("dot.3", LOSS))
    + fused("fused_outer", f"%add.4 = {F8} add(%p, %p)")
    + fused("fused_update", f"%multiply.5 = {F8} multiply(%p, %p)")
    # the inner loop's trip count is read from its condition's bound
    + loop("cond.2", "body.2", 2, [
        f"  %x = {F8} get-tuple-element(%t), index=1",
        fusion("fusion.6", "x", "fused_ssd", SSD),
        "  %i = s32[] get-tuple-element(%t), index=0",
        f"  ROOT %tuple.2 = {LOOP} tuple(%i, %fusion.6)"])
    + loop("cond.1", "body.1", 3, [
        f"  %while.2 = {LOOP} while(%t), condition=%cond.2, body=%body.2, "
        + meta(HVP + "/body/while"),
        f"  %x = {F8} get-tuple-element(%while.2), index=1",
        fusion("fusion.3", "x", "fused_in_proj", IN_PROJ),
        f"  %copy.4 = {F8} copy(%fusion.3)",
        "  %i = s32[] get-tuple-element(%t), index=0",
        f"  ROOT %tuple.1 = {LOOP} tuple(%i, %copy.4)"])
    + ["ENTRY %main.1 (p0: f32[8,8]) -> f32[8,8] {",
       f"  %p0 = {F8} parameter(0)",
       "  %c0 = s32[] constant(0)",
       f"  %tuple.0 = {LOOP} tuple(%c0, %p0)",
       f"  %while.1 = {LOOP} while(%tuple.0), condition=%cond.1, "
       'body=%body.1, backend_config={"known_trip_count":{"n":"3"}}, '
       + meta(HVP),
       fusion("fusion.9", "p0", "fused_loss", LOSS),
       f"  %copy.5 = {F8} copy(%p0)",
       fusion("fusion.10", "copy.5", "fused_update", UPDATE),
       fusion("fusion.11", "fusion.10", "fused_outer", OUTER, root=True),
       "}", ""])


def ev(name, start, dur):
    return (f"%{name} = {name.split('.')[0]}(...)", start * MS, dur * MS)


def small_trace(steps=1):
    """One step a window of 100 ms: the Hessian loop (3 trips of a nested
    loop of 2), the loss, a copy the compiler made, a clone the module
    text lacks, the update and the outer pass."""
    return bt.Trace({
        "/host:CPU": {"python3": [("bench.window", 0, 100 * MS * steps)]},
        "/device:TPU:0": {
            "XLA Modules": [("jit_step_fn(7)", 0, 80 * MS)],
            "XLA Ops": [ev("while.1", 0, 60),
                        ev("while.2", 5, 20), ev("fusion.6", 6, 10),
                        ev("fusion.6", 16, 4),
                        ev("fusion.3", 30, 10), ev("copy.4", 40, 2),
                        ev("fusion.9", 60, 10), ev("copy.5", 70, 1),
                        ev("broadcast.7.clone", 71, 2),
                        ev("fusion.10", 73, 2), ev("fusion.11", 75, 5)]}})


def test_reduction_by_phase_and_part():
    tr = small_trace()
    out = scopes.reduce(MODULE, tr, "/device:TPU:0", steps=1)
    ms = {k: v["ms"] for k, v in out["phases"].items()}
    # the loops' own time (60 − 32 and 20 − 14) and the copy in the loop
    # body go to the loop's phase; the copy at the entry has no metadata
    # and no caller, and the clone is no instruction of the module
    assert ms == pytest.approx({
        "perfed.adapt": 0, "perfed.outer": 5, "perfed.hvp": 60,
        "perfed.loss": 10, "train.update": 2, "semi_sync.eq8": 0,
        "unscoped": 3})
    assert sum(ms.values()) == pytest.approx(out["busy_ms"])
    assert out["busy_ms"] == pytest.approx(tr.busy_s("/device:TPU:0") * 1e3)
    parts = {k: v["ms"] for k, v in out["parts"].items()}
    assert parts == pytest.approx({"remat": 10, "ssm.in_proj": 10,
                                   "ssm.ssd": 19, "ssm.out_proj": 10})
    assert out["top"][0] == ["while.1", pytest.approx(28), "perfed.hvp",
                             None]


def test_time_per_step_divides_by_the_window_steps():
    one = scopes.reduce(MODULE, small_trace(), "/device:TPU:0", steps=1)
    two = scopes.reduce(MODULE, small_trace(), "/device:TPU:0", steps=2)
    assert two["phases"]["perfed.hvp"]["ms"] == pytest.approx(
        one["phases"]["perfed.hvp"]["ms"] / 2)


def test_dot_flops_by_scope_count_loop_trips():
    out = scopes.reduce(MODULE, small_trace(), "/device:TPU:0", steps=1)
    dot = 2 * 8 * 8 * 8
    flops = {k: v["dot_flops"] for k, v in out["phases"].items()}
    # 3 trips of the in_proj dot and 3 × 2 of the nested ssd dot
    assert flops["perfed.hvp"] == 9 * dot
    assert flops["perfed.loss"] == dot
    assert out["parts"]["remat"]["dot_flops"] == 3 * dot
    assert sum(flops.values()) == hlo.analyze_hlo(MODULE)["dot_flops_tc"]


def test_paths_go_by_the_caller_where_an_instruction_names_no_phase():
    paths = scopes.op_paths(MODULE)
    assert paths["copy.4"] == HVP
    assert paths["copy.5"] == ""
    assert scopes.classify(paths["fusion.9"]).phase == "perfed.loss"
    assert "broadcast.7.clone" not in paths


def test_a_module_without_phase_scopes_reads_none():
    bare = re.sub(r'metadata=\{op_name="[^"]*"\}', "", MODULE)
    assert scopes.reduce(bare, small_trace(), "/device:TPU:0", 1) is None


# ---------------------------------------------------------------------------
# the metrics
# ---------------------------------------------------------------------------

def hand_artifact():
    phases = {p: {"ms": 10.0 * (i + 1), "dot_flops": 0.0}
              for i, p in enumerate(scopes.PHASES + (scopes.UNSCOPED,))}
    return {"kind": "train", "scopes": {
        "steps": 2, "busy_ms": sum(v["ms"] for v in phases.values()),
        "phases": phases,
        "parts": {"remat": {"ms": 7.0, "dot_flops": 0.0},
                  "ssm.ssd": {"ms": 5.0, "dot_flops": 0.0}},
        "top": []}}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_metric_reads_its_bucket(metric):
    read = harness.metric_reader(metric)
    art = hand_artifact()
    group, key = METRICS[metric]
    assert read(art) == art["scopes"][group][key]["ms"]
    # a program that names no phase scope reads nothing, as does another
    # kind of cell
    assert read({"kind": "train", "scopes": None}) is None
    assert read({"kind": "serve"}) is None


def test_metrics_are_in_the_spec_with_their_layer():
    spec = harness.spec()
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name in METRICS:
        m = per_layer[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "device_trace", "train_tokens_per_s")
        assert m["workloads"] == [CELL]
        assert m["layer"] == ("models" if name in ("remat_ms", "ssd_scan_ms")
                              else "SPMD step")


# ---------------------------------------------------------------------------
# the program's scopes, compiled on the CPU
# ---------------------------------------------------------------------------

SMALL = (dict(d_model=256, n_layer=2, vocab_size=1024),
         dict(batch=2, seq_len=512))


def small_program(tmp_path, cfg_kw=SMALL[0], wl_kw=SMALL[1]):
    cell = cells.tiny(CELL)
    cell.config = dict(cell.config, **cfg_kw)
    cell.workload = dict(cell.workload, **wl_kw)
    return cell, cell.driver.Program(cells.ctx(cell, tmp_path))


@pytest.fixture(scope="module")
def compiled_small(tmp_path_factory):
    return small_program(tmp_path_factory.mktemp("small"))


@pytest.fixture(scope="module")
def compiled_tiny(tmp_path_factory):
    return small_program(tmp_path_factory.mktemp("tiny"), {}, {})


def test_step_dot_flops_fall_in_phase_scopes(compiled_small):
    cell, program = compiled_small
    text = program.compiled.as_text()
    paths = scopes.op_paths(text)
    by = {}
    for name, f in scopes.operations(text):
        s = scopes.classify(paths.get(name, ""))
        key = (s.phase, s.remat)
        by[key] = by.get(key, 0.0) + f
    total = hlo.analyze_hlo(text)["dot_flops_tc"]
    assert sum(by.values()) == pytest.approx(total, rel=1e-12)
    assert not any(f for (ph, _), f in by.items() if ph == scopes.UNSCOPED)
    seq = cell.workload["seq_len"]
    F = cell.workload["batch"] * seq * \
        cell.family.forward_per_token(cell.config, seq)
    plain = {ph: f / F for (ph, remat), f in by.items() if not remat}
    # a gradient each, 3F; the reported loss is the outer pass's own value,
    # so no work runs under perfed.loss
    assert plain["perfed.adapt"] == pytest.approx(3.0, rel=0.01)
    assert plain["perfed.outer"] == pytest.approx(3.0, rel=0.01)
    assert ("perfed.loss", False) not in by
    # a rename of JAX's rematted_computation would read 0 here
    assert sum(f for (_, remat), f in by.items() if remat) > 0


def strip_metadata(text):
    """HLO text without metadata, every %name numbered by first use."""
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r".*?\n\n", "", text, flags=re.S | re.M)
    text = re.sub(r",?\s*metadata=\{[^{}]*\}", "", text)
    ids = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: ids.setdefault(m.group(0), f"%v{len(ids)}"), text)


def test_scopes_change_metadata_only(compiled_tiny, tmp_path, monkeypatch):
    _, scoped = compiled_tiny
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _, bare = small_program(tmp_path, {}, {})
    a, b = scoped.compiled.as_text(), bare.compiled.as_text()
    assert scopes.reduce(b, small_trace(), "/device:TPU:0", 1) is None
    assert a != b
    assert strip_metadata(a) == strip_metadata(b)


def test_semi_sync_step_carries_eq8_and_eq7_scopes():
    from repro.config import ExperimentConfig, FLConfig, ModelConfig, \
        TrainConfig
    from repro.core import semi_sync
    from repro.models import build_model
    from repro.optim import make_optimizer

    cfg = ExperimentConfig(
        model=ModelConfig(name="mnist_dnn", family="small", d_model=16,
                          vocab_size=10, dtype="float32"),
        fl=FLConfig(alpha=0.02, beta=0.1, staleness_bound=2),
        train=TrainConfig(grad_clip=1.0))
    model = build_model(cfg.model)
    opt = make_optimizer("sgd")
    n = 2
    rng = jax.random.PRNGKey(0)
    state = semi_sync.init_state(model, rng, opt, n)

    def role(shape):
        return jax.ShapeDtypeStruct((n, 4) + shape, jnp.float32)
    one = {"x": role((28, 28)), "y": jax.ShapeDtypeStruct((n, 4), jnp.int32)}
    batches = {"inner": one, "outer": one, "hessian": one}
    text = jax.jit(semi_sync.make_semi_sync_step(model, cfg, opt, n)).lower(
        state, batches, jnp.ones((n,)), rng).compile().as_text()
    phases = {scopes.classify(p).phase
              for p in scopes.op_paths(text).values()}
    assert {"semi_sync.eq8", "perfed.adapt", "perfed.outer",
            "perfed.hvp"} <= phases
    assert "train.update" not in phases and "perfed.loss" not in phases


# ---------------------------------------------------------------------------
# the module from the trace file, as a traced run reads it
# ---------------------------------------------------------------------------

def test_readers_take_the_module_from_the_trace_file(compiled_tiny, tmp_path,
                                                     monkeypatch, capsys):
    """A CPU profile carries the executed module like a TPU one; its device
    events are made here, one per operation of the step, 1 ms each."""
    cell, program = compiled_tiny
    params, pool, rngs = program.inputs(cells.ctx(cell, tmp_path).seed)
    state, _ = program.first_steps(params, pool, rngs)
    bare = jax.jit(lambda x: x * 2)
    jax.block_until_ready(bare(jnp.ones(3)))
    out_dir = tmp_path / ".bench_out"
    jax.profiler.start_trace(str(out_dir / CELL / "trace"))
    state, m = program.compiled(state, pool[3], rngs[3])
    jax.block_until_ready((state, m, bare(jnp.ones(3))))
    jax.profiler.stop_trace()
    monkeypatch.setattr(scopes, "BENCH_OUT", out_dir)

    text = program.compiled.as_text()
    mods = scopes.hlo_modules(scopes.newest_xplane(out_dir))
    # the profile holds every program of the process (the other compiles
    # of this file's fixtures too): one of them is this step, as compiled
    paths = scopes.op_paths(text)
    step = [n for n, p in mods.items() if n.startswith("jit_step_fn(")
            and scopes.op_paths(scopes.module_text(p)) == paths]
    assert len(step) == 1

    names = [n for n, _ in scopes.operations(text)]
    evs = [(f"%{n} = op(...)", i * MS, MS) for i, n in enumerate(names)]

    def art(module):
        return {"kind": "train", "steps": 1, "trace": bt.Trace({
            "/host:CPU": {"python3": [("bench.window", 0, len(evs) * MS)]},
            "/device:TPU:0": {"XLA Modules": [(module, 0, len(evs) * MS)],
                              "XLA Ops": evs}})}

    a = art(step[0])
    got = {m: harness.metric_reader(m)(a) for m in METRICS}
    assert all(v is not None and v >= 0 for v in got.values())
    phases = sum(got[m] for m, (g, _) in METRICS.items() if g == "phases")
    assert phases == pytest.approx(len(evs))
    assert 0 < got["remat_ms"] < phases and 0 < got["ssd_scan_ms"] < phases
    line = [ln for ln in capsys.readouterr().err.splitlines()
            if ln.startswith("bench: scopes ")]
    assert len(line) == 1
    assert json.loads(line[0][len("bench: scopes "):]) == a["scopes"]
    # the same trace timed by a program that names no phase reads nothing
    b = art("jit__lambda(0)")
    assert all(harness.metric_reader(m)(b) is None for m in METRICS)


# ---------------------------------------------------------------------------
# recorded on the chip
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """A traced 10-s window of the cell recorded on a TPU v5e: for each
    phase, the events from its first operation over the next 80 (the
    operations' text cut to name and opcode), the ``op_name`` path of each
    (``op_paths`` of the module the trace carries), and the whole window's
    own device time summed by path."""
    return json.loads((DATA / f"scopes_{CELL}.json").read_text())


def test_recorded_chip_slices_split_their_busy_time(recorded):
    seen = set()
    for sl in recorded["slices"]:
        tr = bt.Trace({p: {ln: [tuple(e) for e in evs]
                           for ln, evs in lines.items()}
                       for p, lines in sl["planes"].items()})
        dev = tr.devices[0]
        b, _ = scopes.device_ms(tr, dev, recorded["op_names"], steps=1)
        assert sum(b.phases.values()) == pytest.approx(
            tr.busy_s(dev) * 1e3, rel=1e-9)
        assert b.phases[sl["phase"]] > 0
        seen.add(sl["phase"])
    assert seen == set(scopes.PHASES) - {"semi_sync.eq8"}


def test_recorded_chip_window_is_scoped(recorded):
    win = recorded["window"]
    ms = {p: 0.0 for p in scopes.PHASES + (scopes.UNSCOPED,)}
    remat = ssd = 0.0
    for path, ns in win["own_ns_by_path"].items():
        s = scopes.classify(path)
        v = ns * 1e-6 / win["steps"]
        ms[s.phase] += v
        remat += v if s.remat else 0.0
        ssd += v if s.part == "ssm.ssd" else 0.0
    busy = win["busy_s"] * 1e3 / win["steps"]
    assert sum(ms.values()) == pytest.approx(busy, rel=1e-6)
    assert ms[scopes.UNSCOPED] < 0.01 * busy
    assert 0 < remat < busy and 0 < ssd < busy
    # the same passes on batches of one size: the outer gradient and the
    # inner adaptation take the same time
    assert ms["perfed.outer"] == pytest.approx(ms["perfed.adapt"], rel=0.02)
