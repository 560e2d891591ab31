"""The program's own key to its device events: ``profiling.scope`` (the one
way to name device work), ``profiling.scope_table`` (every instruction's
scope path, phase and provenance from a compiled program's text) and
``unit_scopes.json``, which ``profiling.trace`` leaves beside a profile.
CPU."""

import json
import os
import re

import jax
import numpy as np
import optax
import pytest

from mpit_tpu.data import Batches
from mpit_tpu.models.transformer import TransformerLM
from mpit_tpu.parallel import DataParallelTrainer
from mpit_tpu.utils import profiling

SCOPES = ("attention", "mlp", "moe_experts", "flash_dq", "loss", "optimizer",
          "elastic")
#: what a tiny GPT-2 block under the sync trainer sets
LM_SCOPES = {"embed", "attn_proj", "attention", "mlp", "head", "loss",
             "grad_exchange", "optimizer"}

BWD = "jit(step)/transpose(jvp(M))/jvp(M)/checkpoint"
PROGRAM = f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8,8], param_1.1: f32[8,8]) -> (f32[8,8], f32[8,8]) {{
  %param_0.1 = f32[8,8]{{1,0}} parameter(0)
  %param_1.1 = f32[8,8]{{1,0}} parameter(1)
  %dot.7 = f32[8,8]{{1,0}} dot(%param_0.1, %param_1.1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{BWD}/Block_1/mlp/dot_general" stack_frame_id=3}}
  %multiply.3 = f32[8,8]{{1,0}} multiply(%dot.7, %param_1.1), metadata={{op_name="jit(step)/optimizer/mul"}}
  ROOT %tuple.9 = (f32[8,8]{{1,0}}, f32[8,8]{{1,0}}) tuple(%dot.7, %multiply.3)
}}

%fused_computation.2 (param_0.2: f32[8,8]) -> f32[8,8] {{
  %param_0.2 = f32[8,8]{{1,0}} parameter(0)
  %transpose.4 = f32[8,8]{{0,1}} transpose(%param_0.2), dimensions={{1,0}}
  ROOT %copy.5 = f32[8,8]{{1,0}} copy(%transpose.4)
}}

%fused_computation.3 (param_0.3: f32[8,8]) -> f32[8,8] {{
  %param_0.3 = f32[8,8]{{1,0}} parameter(0)
  ROOT %tanh.1 = f32[8,8]{{1,0}} tanh(%param_0.3), metadata={{op_name="jit(step)/jvp(M)/Block_0/attention/tanh"}}
}}

%region_0.1 (a.1: f32[], b.1: f32[]) -> f32[] {{
  %a.1 = f32[] parameter(0)
  %b.1 = f32[] parameter(1)
  ROOT %add.40 = f32[] add(%a.1, %b.1), metadata={{op_name="jit(step)/jvp(loss)/reduce_sum"}}
}}

%body.1 (p.1: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {{
  %p.1 = (s32[]{{:T(128)}}, f32[8,8]{{1,0:T(8,128)}}) parameter(0)
  %get-tuple-element.19 = s32[]{{:T(128)}} get-tuple-element(%p.1), index=0
  %get-tuple-element.20 = f32[8,8]{{1,0:T(8,128)}} get-tuple-element(%p.1), index=1
  %add.30 = f32[8,8]{{1,0:T(8,128)}} add(%get-tuple-element.20, %get-tuple-element.20), metadata={{op_name="jit(step)/elastic/while/body/add"}}
  ROOT %tuple.31 = (s32[]{{:T(128)}}, f32[8,8]{{1,0:T(8,128)}}) tuple(%get-tuple-element.19, %add.30)
}}

%cond.1 (p.2: (s32[], f32[8,8])) -> pred[] {{
  %p.2 = (s32[]{{:T(128)}}, f32[8,8]{{1,0:T(8,128)}}) parameter(0)
  %get-tuple-element.21 = s32[]{{:T(128)}} get-tuple-element(%p.2), index=0
  %constant.22 = s32[]{{:T(128)}} constant(4)
  ROOT %compare.23 = pred[]{{:T(512)}} compare(%get-tuple-element.21, %constant.22), direction=LT
}}

ENTRY %main.1 (x.1: f32[8,8], w.1: f32[8,8]) -> (f32[8,8], f32[8,8]) {{
  %x.1 = f32[8,8]{{1,0:T(8,128)}} parameter(0), metadata={{op_name="x"}}
  %w.1 = f32[8,8]{{1,0:T(8,128)}} parameter(1), metadata={{op_name="w"}}
  %copy.1 = f32[8,8]{{0,1:T(8,128)}} copy(%w.1)
  %dot.1 = f32[8,8]{{1,0:T(8,128)}} dot(%x.1, %copy.1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="jit(step)/jvp(M)/Block_0/mlp/dot_general" stack_frame_id=3}}
  %dot.2 = f32[8,8]{{1,0:T(8,128)}} dot(%dot.1, %w.1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{BWD}/rematted_computation/Block_0/mlp/moe_experts/dot_general"}}
  %flash_dq.3 = f32[8,8]{{1,0:T(8,128)}} custom-call(%dot.2), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}/Block_0/attention/jit(_bwd)/flash_dq/pallas_call"}}
  %subtract.4 = f32[8,8]{{1,0:T(8,128)}} subtract(%flash_dq.3, %x.1), metadata={{op_name="jit(step)/transpose(jvp(loss))/sub"}}
  %multiply.5 = f32[8,8]{{1,0:T(8,128)}} multiply(%subtract.4, %w.1), metadata={{op_name="jit(step)/optimizer/mul;while/body/closed_call"}}
  %add.6 = f32[8,8]{{1,0:T(8,128)}} add(%multiply.5, %w.1), metadata={{op_name="jit(step)/jvp(M)/Block_1/mlp/add;jit(step)/optimizer/add"}}
  %copy-start.7 = (f32[8,8]{{1,0:T(8,128)}}, f32[8,8]{{1,0:T(8,128)S(1)}}, u32[]{{:S(2)}}) copy-start(%x.1)
  %copy-done.7 = f32[8,8]{{1,0:T(8,128)S(1)}} copy-done(%copy-start.7)
  %fusion.8 = f32[8,8]{{1,0:T(8,128)}} fusion(%copy-done.7), kind=kLoop, calls=%fused_computation.3
  %fusion.9 = (f32[8,8]{{1,0:T(8,128)}}, f32[8,8]{{1,0:T(8,128)}}) fusion(%dot.1, /*index=1*/%w.1), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{BWD}/Block_1/mlp/dot_general" stack_frame_id=3}}
  %get-tuple-element.10 = f32[8,8]{{1,0:T(8,128)}} get-tuple-element(%fusion.9), index=0
  %copy.11 = f32[8,8]{{0,1:T(8,128)}} copy(%get-tuple-element.10)
  %fusion.12 = f32[8,8]{{1,0:T(8,128)}} fusion(%x.1), kind=kLoop, calls=%fused_computation.2
  %copy.13 = f32[8,8]{{0,1:T(8,128)}} copy(%x.1)
  %dot.14 = f32[8,8]{{1,0:T(8,128)}} dot(%copy.13, %add.6), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="jit(step)/jvp(M)/Block_0/mlp/dot_general"}}
  %tanh.15 = f32[8,8]{{1,0:T(8,128)}} tanh(%copy.13), metadata={{op_name="jit(step)/jvp(M)/Block_0/attention/tanh"}}
  %constant.16 = f32[]{{:T(128)}} constant(0)
  %reduce.17 = f32[]{{:T(128)}} reduce(%tanh.15, %constant.16), dimensions={{0,1}}, to_apply=%region_0.1, metadata={{op_name="jit(step)/jvp(loss)/reduce_sum"}}
  %multiply.18 = f32[8,8]{{1,0:T(8,128)}} multiply(%x.1, %x.1), metadata={{op_name="jit(step)/jvp(M)/LayerNorm_0/mul"}}
  %copy.34 = f32[8,8]{{0,1:T(8,128)}} copy(%x.1)
  %ragged-dot-none.32 = f32[8,8]{{1,0:T(8,128)}} custom-call(%dot.2, %copy.34), custom_call_target="tpu_custom_call"
  %add.33 = f32[8,8]{{1,0:T(8,128)}} add(%ragged-dot-none.32, %x.1), metadata={{op_name="jit(step)/jvp(M)/Block_0/attention/add"}}
  %copy.35 = f32[8,8]{{0,1:T(8,128)}} copy(%w.1)
  %tuple.36 = (f32[8,8]{{0,1:T(8,128)}}, f32[8,8]{{1,0:T(8,128)}}) tuple(%copy.35, %dot.1)
  %constant.24 = s32[]{{:T(128)}} constant(0)
  %tuple.25 = (s32[]{{:T(128)}}, f32[8,8]{{1,0:T(8,128)}}) tuple(%constant.24, %fusion.8)
  %while.26 = (s32[]{{:T(128)}}, f32[8,8]{{1,0:T(8,128)}}) while(%tuple.25), condition=%cond.1, body=%body.1, metadata={{op_name="jit(step)/elastic/while"}}
  %get-tuple-element.27 = f32[8,8]{{1,0:T(8,128)}} get-tuple-element(%while.26), index=1
  ROOT %tuple.28 = (f32[8,8]{{1,0:T(8,128)}}, f32[8,8]{{1,0:T(8,128)}}, f32[8,8]{{1,0:T(8,128)}}, f32[]{{:T(128)}}, f32[8,8]{{1,0:T(8,128)}}) tuple(%copy.11, %fusion.12, %get-tuple-element.27, %reduce.17, %multiply.18, /*index=5*/%add.33, %tuple.36)
}}
"""


@pytest.fixture(scope="module")
def table():
    return profiling.scope_table(PROGRAM, SCOPES)


def _row(table, name, **want):
    row = table[name]
    for key, value in want.items():
        assert row[key] == value, (name, key, row)
    return row


def test_scope_registers_its_name_and_is_a_named_scope():
    with profiling.scope("t_scope_b"), profiling.scope("t_scope_a"):
        pass
    names = profiling.scopes()
    assert names == sorted(names) and {"t_scope_a", "t_scope_b"} <= set(names)
    profiling.reset()  # a traced program keeps its names: so does the set
    assert "t_scope_a" in profiling.scopes()
    text = jax.jit(_scoped).lower(1.0).compile().as_text()
    assert re.search(r'op_name="jit\([^"]*/t_scope_c/', text)


def _scoped(x):
    with profiling.scope("t_scope_c"):
        return jax.numpy.sin(x) * 2


@pytest.mark.parametrize("name,want", [
    ("dot.1", dict(path=["mlp"], layer="Block_0", phase="forward",
                   opcode="dot", how="own")),
    ("dot.2", dict(path=["mlp", "moe_experts"], layer="Block_0",
                   phase="recompute", how="own")),
    ("flash_dq.3", dict(path=["attention", "flash_dq"], phase="backward",
                        opcode="custom-call", how="own")),
    # a scope set outside a flax module is wrapped itself
    ("subtract.4", dict(path=["loss"], layer=None, phase="backward")),
    ("add.30", dict(path=["elastic"], phase="update", how="own")),
    ("while.26", dict(path=["elastic"], phase="update", opcode="while")),
    ("reduce.17", dict(path=["loss"], phase="forward", opcode="reduce")),
    ("add.40", dict(path=["loss"], phase="forward", how="own")),
])
def test_an_op_name_gives_path_layer_phase_and_opcode(table, name, want):
    _row(table, name, **want)


def test_two_joined_op_names_are_read_apart(table):
    # the second holds no scope and says no phase: the first one's stand
    _row(table, "multiply.5", path=["optimizer"], phase="update", how="own")
    # both hold one: the last, as program_spans.scope_of finds it; the
    # phases differ
    _row(table, "add.6", path=["optimizer"], layer=None, phase="mixed")


@pytest.mark.parametrize("name,want", [
    # a copy the consumer asked for
    ("copy.1", dict(path=["mlp"], layer="Block_0", phase="forward",
                    opcode="copy", how="user")),
    # both halves of an asynchronous copy, to a fusion named by its parts
    ("copy-start.7", dict(path=["attention"], phase="forward", how="user",
                          opcode="copy-start")),
    ("copy-done.7", dict(path=["attention"], phase="forward", how="user")),
    ("fusion.8", dict(path=["attention"], layer="Block_0", phase="forward",
                      how="fused", holds=[], fused=["parameter", "tanh"])),
    # nothing named reads them: what made their operand
    ("get-tuple-element.10", dict(path=["mlp"], layer="Block_1",
                                  phase="backward", how="operand")),
    ("copy.11", dict(path=["mlp"], phase="backward", how="operand")),
    # a kernel the compiler made computes: named by what made its operands,
    # whatever reads its result; and its own copy after it, chained
    ("ragged-dot-none.32", dict(path=["mlp", "moe_experts"], layer="Block_0",
                                phase="recompute", opcode="custom-call",
                                how="operand")),
    ("copy.34", dict(path=["mlp", "moe_experts"], phase="recompute",
                     how="user")),
    # a tuple named by one operand does not name the other
    ("tuple.36", dict(path=["mlp"], phase="forward", how="operand")),
    ("copy.35", dict(path=[], phase="unnamed", how="none")),
    # a loop's own name reaches what is packed for it
    ("constant.24", dict(path=["elastic"], phase="update", how="user")),
    # inside a loop's body, from the body's own instructions
    ("get-tuple-element.20", dict(path=["elastic"], phase="update",
                                  how="user")),
    # readers that disagree (mlp, attention) and a parameter behind it
    ("copy.13", dict(path=[], layer=None, phase="unnamed", how="none")),
    ("fusion.12", dict(path=[], phase="unnamed", how="none", holds=[],
                       fused=["copy", "parameter", "transpose"])),
    # no scope, but its own op_name says the phase
    ("multiply.18", dict(path=[], phase="forward", how="none")),
    ("x.1", dict(path=[], phase="unnamed", how="none", opcode="parameter")),
])
def test_a_nameless_instruction_inherits_by_one_rule(table, name, want):
    _row(table, name, **want)


def test_a_fusion_says_which_other_scopes_it_holds(table):
    row = _row(table, "fusion.9", path=["mlp"], layer="Block_1",
               phase="backward", opcode="fusion", how="own",
               holds=[["optimizer", "update"]])
    assert row["fused"] == ["dot", "multiply", "parameter", "tuple"]
    # a fused computation's instructions, which no event names: own or none
    _row(table, "multiply.3", path=["optimizer"], phase="update", how="own")
    _row(table, "param_0.1", path=[], how="none")
    assert "holds" not in table["dot.1"]


def test_every_instruction_of_every_computation_has_a_whole_row(table):
    names = re.findall(r"^\s+(?:ROOT )?%([\w.\-]+) = ", PROGRAM, re.M)
    assert set(table) == set(names) and len(names) == len(set(names))
    for row in table.values():
        assert {"path", "layer", "phase", "opcode", "how"} <= set(row)
        assert row["phase"] in profiling.PHASES
        assert row["how"] in ("own", "fused", "user", "operand", "none")
        assert bool(row["path"]) == (row["how"] != "none")
    assert json.loads(json.dumps(table)) == table


def _tokens(n=32, t=16, vocab=61, seed=0):
    x = np.random.default_rng(seed).integers(0, vocab, (n, t)).astype(np.int32)
    return x, np.roll(x, -1, axis=1)


def _sync_lm(topo, remat):
    model = TransformerLM(vocab_size=61, num_layers=2, d_model=32,
                          num_heads=4, max_len=16, remat=remat)
    trainer = DataParallelTrainer(model, optax.adamw(1e-3), topo,
                                  donate_state=False)
    x, y = _tokens()
    return trainer, trainer.init_state(jax.random.key(0), x[:2]), x, y


@pytest.mark.parametrize("remat", [True, False])
def test_the_compiled_sync_step_reads_into_scopes_and_phases(topo8, remat):
    trainer, state, x, y = _sync_lm(topo8, remat)
    text = trainer._step.lower(state, x[:16], y[:16]).compile().as_text()
    assert LM_SCOPES <= set(profiling.scopes())
    table = profiling.scope_table(text, profiling.scopes())
    found = {s for row in table.values() for s in row["path"]}
    assert found == LM_SCOPES
    phases = {row["phase"] for row in table.values()}
    assert {"forward", "backward", "update"} <= phases
    assert ("recompute" in phases) == remat
    by_scope = {}
    for row in table.values():
        if row["how"] == "own":
            by_scope.setdefault(row["path"][-1], set()).add(row["phase"])
            if row["path"][0] in ("attention", "attn_proj", "mlp"):
                assert row["layer"] in ("Block_0", "Block_1"), row
    assert by_scope["optimizer"] == by_scope["grad_exchange"] == {"update"}
    assert {"forward", "backward"} <= by_scope["mlp"]
    assert {"forward", "backward"} <= by_scope["embed"]
    if remat:
        assert "recompute" in by_scope["mlp"]


def test_trace_leaves_the_units_table_beside_the_profile(topo8, tmp_path):
    profiling.reset()
    trainer, state, x, y = _sync_lm(topo8, remat=True)
    with profiling.trace(str(tmp_path)):
        state, _ = trainer.fit(Batches(x, y, global_batch=16), state, epochs=1)
        jax.block_until_ready(state)
    with open(tmp_path / profiling.UNIT_SCOPES_FILE) as f:
        left = json.load(f)
    assert left["instructions"] == profiling.unit_scope_table()
    assert LM_SCOPES <= set(left["scopes"])
    assert any(row["phase"] == "recompute"
               for row in left["instructions"].values())
    # one text, one table: a second reader compiles and parses nothing
    assert profiling.unit_scope_table() is profiling.unit_scope_table()
    profiling.reset()
    assert profiling.unit_scope_table() is None  # no fit loop since


def test_trace_without_a_unit_or_a_directory_leaves_nothing(tmp_path):
    profiling.reset()
    with profiling.trace(None):
        pass
    with profiling.trace(str(tmp_path)):
        pass
    assert not os.path.exists(tmp_path / profiling.UNIT_SCOPES_FILE)


def test_no_named_scope_is_left_outside_profiling():
    root = os.path.dirname(os.path.abspath(profiling.__file__))
    package = os.path.dirname(root)
    left = []
    for folder, _, files in os.walk(package):
        for name in files:
            path = os.path.join(folder, name)
            if name.endswith(".py") and path != profiling.__file__:
                with open(path) as f:
                    if "jax.named_scope(" in f.read():
                        left.append(os.path.relpath(path, package))
    assert left == []
