"""``benchmark/lib/phase_spans.py``: the reduction of a device plane against
the program's scope table, by hand on a plane and a table built here; the
four readers on runs that hold nothing for them; their files against
``BENCHMARK.json``. CPU, seconds."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import phase_spans  # noqa: E402

NEW = ["recompute_ms_unit", "layout_copy_ms_unit", "unscoped_ms_unit",
       "optimizer_fused_ms_unit"]
MS = 1e6  # a millisecond of the trace's nanoseconds


def row(path, phase, opcode="fusion", how="own", layer=None, **more):
    return {"path": path, "layer": layer, "phase": phase, "opcode": opcode,
            "how": how, **more}


TABLE = {
    "fusion.1": row(["attn_proj"], "forward", layer="Block_0",
                    fused=["dot", "parameter"], holds=[]),
    "dot.2": row(["mlp", "moe_experts"], "recompute", opcode="dot",
                 layer="Block_0"),
    # a weight gradient that XLA gave the weight's update too
    "fusion.3": row(["mlp"], "backward", layer="Block_0",
                    fused=["dot", "multiply", "parameter"],
                    holds=[["optimizer", "update"]]),
    "fusion.4": row(["optimizer"], "update", fused=["add", "parameter"],
                    holds=[]),
    # a fusion under optimizer that holds another scope: not the complement
    "fusion.5": row(["optimizer"], "mixed", fused=["add", "parameter"],
                    holds=[["mlp", "backward"]]),
    "copy.6": row(["mlp"], "forward", opcode="copy", how="user",
                  layer="Block_0"),
    "copy-start.7": row([], "unnamed", opcode="copy-start", how="none"),
    "copy-done.7": row([], "unnamed", opcode="copy-done", how="none"),
    # nothing but movement inside, and arithmetic inside
    "fusion.8": row(["embed"], "backward", how="operand",
                    fused=["bitcast", "parameter", "transpose"], holds=[]),
    "fusion.9": row([], "forward", how="none",
                    fused=["convert", "parameter"], holds=[]),
    "while.10": row(["elastic"], "update", opcode="while"),
    "add.11": row(["elastic"], "update", opcode="add"),
    "ragged-dot.12": row(["mlp", "moe_experts"], "forward",
                         opcode="custom-call", how="user", layer="Block_1"),
}
SCOPES = ["attn_proj", "elastic", "embed", "mlp", "moe_experts", "optimizer"]

# one unit: (instruction, start ms, duration ms); the loop's body lies inside
# the loop's event
UNIT = [
    ("fusion.1", 0, 10), ("dot.2", 10, 20), ("fusion.3", 30, 8),
    ("fusion.4", 38, 1), ("fusion.5", 39, 2), ("copy.6", 41, 3),
    ("copy-start.7", 44, 0.5), ("copy-done.7", 44.5, 0.25),
    ("fusion.8", 45, 4), ("fusion.9", 49, 5),
    ("while.10", 54, 12), ("add.11", 55, 5), ("add.11", 60, 5),
    ("ragged-dot.12", 66, 6), ("not.in.the.table", 72, 1),
]
UNIT_MS = 80  # a unit's module event; 7 ms of it the device idles


def plane(units=2):
    modules = [("jit_init", -50 * MS, 20 * MS)]
    ops = [("%fusion.77 = f32[8] fusion(%p)", -50 * MS, 20 * MS)]
    for k in range(units):
        at = k * 100 * MS
        modules.append(("jit_step", at, UNIT_MS * MS))
        ops += [(f"%{name} = f32[8]{{0}} op(%p)", at + s * MS, d * MS)
                for name, s, d in UNIT]
    return {"XLA Modules": modules, "XLA Ops": ops}


@pytest.fixture(scope="module")
def reduced():
    return phase_spans.reduce(plane(), TABLE, SCOPES, top=3)


def test_phases_add_up_to_busy_time_and_a_loop_is_not_counted_twice(reduced):
    assert reduced["units"] == 2
    phases = reduced["phase_ms_unit"]
    assert list(phases) == list(phase_spans.PHASES)
    # jit_init's 20 ms are no unit's: spread over the two units like the rest
    busy = 10 + 20 + 8 + 1 + 2 + 3 + 0.75 + 4 + 5 + 12 + 6 + 1 + 20 / 2
    assert reduced["busy_ms_unit"] == pytest.approx(busy)
    assert sum(phases.values()) == pytest.approx(busy)
    assert phases["forward"] == pytest.approx(10 + 3 + 5 + 6)
    assert phases["recompute"] == pytest.approx(20)
    assert phases["backward"] == pytest.approx(8 + 4)
    # the loop's 12 ms: 2 of its own and 10 of its body, not 22
    assert phases["update"] == pytest.approx(1 + 12)
    assert phases["mixed"] == pytest.approx(2)
    assert phases["unnamed"] == pytest.approx(0.75 + 1 + 20 / 2)
    assert reduced["inner_ms_unit"]["elastic"] == {"update": pytest.approx(12)}


def test_the_four_metrics_by_hand(reduced):
    assert reduced["recompute_ms_unit"] == pytest.approx(20)
    # copy, copy-start, copy-done and the fusion of movement alone
    assert reduced["layout_copy_ms_unit"] == pytest.approx(3 + 0.75 + 4)
    # empty paths: the async copy, fusion.9, the two unknown instructions
    assert reduced["unscoped_ms_unit"] == pytest.approx(0.75 + 5 + 1 + 10)
    # fusion.3 alone: fusion.5 holds mlp under optimizer, not the reverse
    assert reduced["optimizer_fused_ms_unit"] == pytest.approx(8)


def test_the_breakdowns_by_scope_layer_and_provenance(reduced):
    assert reduced["outer_ms_unit"]["mlp"] == {
        "backward": pytest.approx(8), "forward": pytest.approx(3 + 6),
        "recompute": pytest.approx(20)}
    assert reduced["inner_ms_unit"]["moe_experts"] == {
        "forward": pytest.approx(6), "recompute": pytest.approx(20)}
    assert reduced["inner_ms_unit"][phase_spans.UNSCOPED]["forward"] == \
        pytest.approx(5)
    assert reduced["layer_ms_unit"] == {
        "Block_0": pytest.approx(10 + 20 + 8 + 3),
        "Block_1": pytest.approx(6),
        "none": pytest.approx(1 + 2 + 0.75 + 4 + 5 + 12 + 1 + 10)}
    assert reduced["how_ms_unit"] == {
        "none": pytest.approx(0.75 + 5 + 1 + 10),
        "operand": pytest.approx(4),
        "own": pytest.approx(10 + 20 + 8 + 1 + 2 + 12),
        "user": pytest.approx(3 + 6)}
    # what program_spans' accepted scopes read: own rows, last accepted scope
    assert reduced["own_accepted_ms_unit"] == {
        "attn_proj": pytest.approx(10), "elastic": pytest.approx(12),
        "mlp": pytest.approx(20 + 8), "optimizer": pytest.approx(1 + 2)}
    assert [c[0] for c in reduced["copies_top"]] == [
        "fusion.8", "copy.6", "copy-start.7"]
    assert reduced["copies_top"][0] == [
        "fusion.8", "fusion", "operand", "embed", "backward",
        pytest.approx(4)]
    assert [h[0] for h in reduced["holding_top"]] == ["fusion.3", "fusion.5"]
    assert reduced["holding_top"][0][3] == [["optimizer", "update"]]
    assert reduced["scopes"] == SCOPES
    assert json.loads(json.dumps(reduced)) == reduced


def test_what_counts_as_movement_and_as_a_held_update():
    assert phase_spans.moves_only(TABLE["copy.6"])
    assert phase_spans.moves_only(TABLE["copy-done.7"])
    assert phase_spans.moves_only(TABLE["fusion.8"])
    assert not phase_spans.moves_only(TABLE["fusion.9"])
    assert not phase_spans.moves_only(TABLE["dot.2"])
    assert not phase_spans.moves_only(row([], "unnamed", how="none"))
    assert phase_spans.holds_optimizer(TABLE["fusion.3"])
    assert not phase_spans.holds_optimizer(TABLE["fusion.4"])
    assert not phase_spans.holds_optimizer(TABLE["fusion.5"])
    # a fusion under no scope that holds an update is another scope's too
    assert phase_spans.holds_optimizer(
        row([], "unnamed", how="none", holds=[["optimizer", "update"]]))


@pytest.mark.parametrize("run", [{}, {"trace": None}, {"trace": {}},
                                 {"trace": {"busy_s": 1.0}}])
@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_run_without_its_sources(name, run):
    """No trace; and a trace in a process whose fit loop remembered no unit
    (as at a commit whose program has no table): None, nothing raised."""
    phase_spans.traced.cache_clear()
    read = importlib.import_module(f"benchmark.readers.{name}").read
    assert read(run) is None


def test_a_program_without_a_table_reads_as_none(monkeypatch):
    from mpit_tpu.utils import profiling

    monkeypatch.delattr(profiling, "unit_scope_table")
    assert phase_spans.program_table() == (None, None)


def test_a_metric_is_left_out_where_the_program_has_nothing_of_the_kind(
        monkeypatch):
    out = phase_spans.reduce(plane(), TABLE, SCOPES)
    monkeypatch.setattr(phase_spans, "traced", lambda: out)
    run = {"trace": {"busy_s": 1.0}}
    assert phase_spans.metric(run, "recompute_ms_unit") == pytest.approx(20)
    assert phase_spans.metric(run, "optimizer_fused_ms_unit") == \
        pytest.approx(8)
    plain = {k: v for k, v in TABLE.items() if v["phase"] != "recompute"}
    out = phase_spans.reduce(plane(), plain, ["mlp"])
    assert phase_spans.metric(run, "recompute_ms_unit") is None  # no remat
    assert phase_spans.metric(run, "optimizer_fused_ms_unit") is None
    assert phase_spans.metric(run, "layout_copy_ms_unit") >= 0
    assert phase_spans.metric(run, "unscoped_ms_unit") >= 0


def test_the_four_files_agree_with_the_manifest():
    """Found by name; each lists the cells it is read in, and a later cell is
    held to none of them: ``recompute_ms_unit`` names remat cells only, the
    other three any cells."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    assert set(NEW) <= set(per_layer)
    for name in NEW:
        entry = per_layer[name]
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               f"{name}.json")) as f:
            own = json.load(f)
        # an explicit list, so that no later cell is held to the metric
        assert own["cells"] == entry["workloads"]
        assert set(entry["workloads"]) <= set(cells)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert own[key] == entry[key]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["moves"]) == ("ms", "lower", "device_trace",
                                    "samples_per_s_chip")
        assert own["reader"] == f"{name}:read"
        assert len(own["what"]) > 40
    remat = []  # the cells whose job trains with remat: the config's, or its own
    for w in manifest["workloads"]:
        config = next(c for c in manifest["configs"] if c["name"] == w["config"])
        with open(os.path.join(ROOT, config["file"])) as f:
            fields = dict(json.load(f)["train_config"])
        with open(os.path.join(ROOT, "benchmark", "workloads",
                               f"{w['name']}.json")) as f:
            fields.update(json.load(f).get("train_config", {}))
        if fields.get("remat"):
            remat.append(w["name"])
    assert set(per_layer["recompute_ms_unit"]["workloads"]) <= set(remat)
    for name in NEW[1:]:
        assert set(per_layer[name]["workloads"]) <= set(cells)
