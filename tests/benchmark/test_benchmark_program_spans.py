"""``benchmark/lib/program_spans.py``: the scope reduction and the named idle
time on planes built by hand, the program text's ``op_name`` map, the choice
of this process's own trace file, and the CPU rehearsal reading the program's
registry. CPU, seconds (the rehearsal is a child process)."""

import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import program_spans  # noqa: E402

NEW = ["input_path_ms_unit", "dispatch_host_ms_unit", "init_state_s",
       "attention_ms_unit", "mlp_ms_unit", "head_loss_ms_unit",
       "optimizer_ms_unit", "elastic_ms_unit", "idle_unnamed_pct"]
STEP = "jit(round_step)/shard_map/while/body/closed_call"


@pytest.mark.parametrize("op_name, scope", [
    (f"{STEP}/jvp(TransformerLM)/Block_3/attention/bqhd,bkhd->bhqk/dot_general",
     "attention"),
    (f"{STEP}/transpose(jvp(TransformerLM))/Block_3/mlp/Dense_2/dot_general", "mlp"),
    (f"{STEP}/transpose(jvp(loss))/reduce_sum", "loss"),  # wrapped by jax
    (f"{STEP}/optimizer/mul", "optimizer"),
    ("elastic/psum", "elastic"),  # no prefix at all
    # under two scopes' prefix: the innermost, which is the last, names it
    (f"{STEP}/optimizer/transpose(jvp(TransformerLM))/head/btd,vd->btv/dot_general",
     "head"),
    (f"{STEP}/jvp(TransformerLM)/Embed_0/jit(_take)/gather", None),
    (f"{STEP}/jvp(TransformerLM)/Block_3/attention_like/mul", None),
    ("", None),
])
def test_an_event_belongs_to_the_last_scope_on_its_path(op_name, scope):
    assert program_spans.scope_of(op_name) == scope


PROGRAM_TEXT = '''
HloModule jit_round_step, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.9 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(round_step)/shard_map/while/body/closed_call/optimizer/mul" source_file="easgd.py" source_line=118}
}

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0), metadata={op_name="state.center"}
  %fusion.1 = f32[8]{0:T(128)} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(round_step)/shard_map/while/body/closed_call/optimizer/mul" source_file="easgd.py" source_line=118}
  %copy-start.3 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%fusion.1)
  ROOT %all-reduce.1 = f32[8]{0} all-reduce(%fusion.1), replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(round_step)/shard_map/elastic/psum"}
}
'''


def test_the_program_text_gives_each_instruction_its_op_name():
    names = program_spans.op_names_of(PROGRAM_TEXT)
    assert names["fusion.1"].endswith("/optimizer/mul")
    assert names["all-reduce.1"] == "jit(round_step)/shard_map/elastic/psum"
    assert "copy-start.3" not in names  # the compiler's own: no metadata
    assert program_spans.instruction(
        "%fusion.1 = f32[8]{0:T(128)} fusion(f32[8] %p), kind=kLoop") == "fusion.1"


OP_NAMES = {
    "fusion.1": f"{STEP}/jvp(TransformerLM)/Block_0/attention/mul",
    "fusion.2": f"{STEP}/transpose(jvp(TransformerLM))/Block_0/mlp/Dense_2/dot_general",
    "fusion.3": f"{STEP}/optimizer/transpose(jvp(TransformerLM))/head/dot_general",
    "fusion.4": f"{STEP}/optimizer/add",
    "while.1": "jit(round_step)/shard_map/while",
    "all-reduce.1": "jit(round_step)/shard_map/elastic/psum",
    "fusion.5": f"{STEP}/jvp(TransformerLM)/Embed_0/jit(_take)/gather",
}


def hand_built_planes():
    """Two chips, two runs of ``jit_round_step`` each, times in ns.

    Chip 0, each run: a ``%while`` of 3000 ns around ``%fusion.1``
    (attention, 1000), ``%fusion.2`` (mlp, 800) and ``%fusion.3`` (head under
    the optimizer's prefix, 700), so 500 of its own; then ``%fusion.4``
    (optimizer, 400), ``%all-reduce.1`` (elastic, 300), ``%fusion.5`` (the
    embedding, no scope, 200) and ``%copy-start.3`` (no op_name at all, 100):
    4000 ns busy a run. A 12 ns program of another name runs in between.
    Chip 1 idles 5000-6500 of its span 1000-10000: 4800-5750 under
    ``mpit.fit.callback`` with ``bench.wait`` nested in it from 5200, then
    250 ns under nothing, then ``mpit.fit.stage`` from 6000 on."""
    def run(t):
        return [
            ("%while.1 = (f32[8]) while(...)", t, 3000.0),
            ("%fusion.1 = f32[8] fusion(...)", t + 100, 1000.0),
            ("%fusion.2 = f32[8] fusion(...)", t + 1200, 800.0),
            ("%fusion.3 = f32[8] fusion(...)", t + 2100, 700.0),
            ("%fusion.4 = f32[8] fusion(...)", t + 3000, 400.0),
            ("%all-reduce.1 = f32[8] all-reduce(...)", t + 3400, 300.0),
            ("%fusion.5 = f32[8] fusion(...)", t + 3700, 200.0),
            ("%copy-start.3 = (f32[8]) copy-start(...)", t + 3900, 100.0),
        ]
    return {
        "/device:TPU:0": {
            "XLA Modules": [("jit_round_step(1)", 1000.0, 4000.0),
                            ("jit_copy(2)", 5100.0, 12.0),
                            ("jit_round_step(1)", 6000.0, 4000.0)],
            "XLA Ops": run(1000.0) + [("%copy.9 = f32[8] copy(...)", 5100.0, 12.0)]
            + run(6000.0),
        },
        "/device:TPU:1": {
            "XLA Modules": [("jit_round_step(1)", 1000.0, 4000.0),
                            ("jit_round_step(1)", 6500.0, 3500.0)],
            "XLA Ops": [("%fusion.1 = f32[8] fusion(...)", 1000.0, 4000.0),
                        ("%fusion.1 = f32[8] fusion(...)", 6500.0, 3500.0)],
        },
        "/host:CPU": {
            "python3": [("mpit.fit.callback", 4800.0, 950.0),
                        ("bench.wait", 5200.0, 500.0),
                        ("mpit.fit.stage", 6000.0, 900.0)],
        },
    }


def test_scopes_by_hand():
    out = program_spans.reduce(hand_built_planes(), OP_NAMES)
    assert out["program"] == "jit_round_step(1)" and out["units"] == 2
    ms = out["scope_ms_unit"]
    assert ms["attention"] == pytest.approx(1000e-6)
    assert ms["mlp"] == pytest.approx(800e-6)
    assert ms["head"] == pytest.approx(700e-6)  # not the optimizer's
    assert ms["optimizer"] == pytest.approx(400e-6)
    assert ms["elastic"] == pytest.approx(300e-6)
    # the while's own 500, the embedding, the copy-start and the other
    # program's 12 ns over two units: the body is not counted twice
    assert ms["unscoped"] == pytest.approx((500 + 200 + 100 + 6) * 1e-6)
    assert sum(ms.values()) == pytest.approx(out["busy_ms_unit"])
    assert out["busy_ms_unit"] == pytest.approx(4006e-6)
    assert out["unscoped_top"][0] == ["jit(round_step)/shard_map/while",
                                      pytest.approx(500e-6)]
    assert "copy-start.3" in [what for what, _ in out["unscoped_top"]]
    assert out["top_ops"][0] == ["fusion.1", "attention", OP_NAMES["fusion.1"][-80:],
                                 pytest.approx(1000e-6)]
    assert out["top_ops"][2][:2] == ["fusion.3", "head"]
    assert out["scopes_in_program"] == ["attention", "elastic", "head", "mlp",
                                        "optimizer"]


def test_idle_time_is_named_by_the_innermost_span_that_covers_it():
    out = program_spans.reduce(hand_built_planes(), OP_NAMES)
    # chip 1 idles most: 1500 of 9000 ns
    by = out["idle_s_by_span"]
    assert by["mpit.fit.callback"] == pytest.approx(250e-9)  # 5000-5200, 5700-5750
    assert by["bench.wait"] == pytest.approx(500e-9)  # 5200-5700, nested
    assert by[program_spans.NO_SPAN] == pytest.approx(250e-9)  # 5750-6000
    assert by["mpit.fit.stage"] == pytest.approx(500e-9)  # 6000-6500
    assert out["idle_unnamed_pct"] == pytest.approx(100 * 250 / 9000)
    assert out["longest_gap"]["seconds"] == pytest.approx(1500e-9)


def test_a_trace_without_a_device_plane_reads_as_nothing():
    planes = {"/host:CPU": hand_built_planes()["/host:CPU"]}
    assert program_spans.reduce(planes, OP_NAMES) is None


def test_own_trace_is_the_named_cells_newest_and_never_an_older_process(
        tmp_path, monkeypatch):
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))

    def xplane(cell, run, age_s=0.0):
        where = tmp_path / ".bench_out" / cell / "trace" / "plugins" / "profile" / run
        where.mkdir(parents=True)
        path = where / "host.xplane.pb"
        path.write_bytes(b"")
        stamp = time.time() - age_s
        os.utime(path, (stamp, stamp))
        return str(path)

    assert program_spans.own_xplane(["run_cell.py"]) is None
    mine = xplane("cell_a", "r2")
    xplane("cell_a", "r1", age_s=1.0)
    other = xplane("cell_b", "r1")
    argv = ["run_cell.py", "--workload", "cell_a", "--seed", "1"]
    assert program_spans.own_xplane(argv) == mine
    assert program_spans.own_xplane(["run_cell.py", "--workload=cell_b"]) == other
    assert program_spans.own_xplane(["run_cell.py"]) in (mine, other)
    monkeypatch.setattr(program_spans, "process_started_at",
                        lambda: time.time() + 60.0)
    assert program_spans.own_xplane(argv) is None  # written before this process


def test_the_process_start_is_in_the_recent_past():
    assert 0.0 <= time.time() - program_spans.process_started_at() < 6 * 3600


def test_readers_find_nothing_in_a_run_that_holds_nothing(monkeypatch):
    import importlib

    monkeypatch.setattr(program_spans, "registry", lambda: {})
    for name in NEW:
        read = importlib.import_module(f"benchmark.readers.{name}").read
        assert read({"trace": None}) is None, name


def test_the_manifest_holds_the_nine_metrics_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert set(NEW) <= set(per_layer)
    for name in NEW:
        assert "workloads" not in per_layer[name]
        assert per_layer[name]["better"] == "lower"
    assert per_layer["init_state_s"]["moves"] == "setup_s"
    assert per_layer["elastic_ms_unit"]["layer"] == "World"


def test_a_traced_rehearsal_reads_the_programs_registry():
    """No device plane on the CPU: the three host-clock metrics report, the
    six trace metrics are left out, and nothing raises."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, "benchmark/run_cell.py", "--workload",
         "gpt2s_easgd_1chip", "--seed", "2147483693", "--seconds", "3",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(NEW[:3]) <= set(line["metrics"])
    assert not set(NEW[3:]) & set(line["metrics"])
    for name in NEW[:3]:
        assert line["metrics"][name]["value"] > 0
    assert (line["metrics"]["input_path_ms_unit"]["value"]
            < line["metrics"]["unit_ms_p50"]["value"])
