"""What PR 34 added to the benchmark: the configuration's file against the
catalog's row, the parameter recount, the benchmark's copy of the reference
against the program's, the recurrence's operation and byte counts against
counts by hand, the new reduction on planes built by hand, the readers on a
fixture, ``decide`` on readings from the chip, and the new cell's traced
rehearsal. CPU."""

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import delta_kernels, scope_spans  # noqa: E402

CELL = "olmo_hybrid_sync_1chip_8k"
NAME = "olmo-hybrid-7b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["linattn_ms_unit", "delta_ms_unit", "delta_roofline_pct",
       "delta_chunk_log_decay_min"]
#: accepted per-layer metrics without a ``workloads`` list: the new cell's
#: traced line has to hold every one (the chip's; the CPU reads the first six)
UNLISTED = ["compile_s", "input_host_ms_unit", "unit_ms_p50", "mfu_pct",
            "device_idle_pct", "input_path_ms_unit", "dispatch_host_ms_unit",
            "init_state_s", "attention_ms_unit", "mlp_ms_unit",
            "head_loss_ms_unit", "optimizer_ms_unit", "idle_unnamed_pct"]
REDUCED = {"num_hidden_layers": 4, "vocab_size": 12544}
PERIOD = ["linear_attention"] * 3 + ["full_attention"]


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = load("benchmark", "configs", f"{NAME}.json")
MANIFEST = load("BENCHMARK.json")
JOB = load("benchmark", "workloads", f"{CELL}.json")
#: a sound run's readings at the published widths, the three controls' and a
#: traced run's line (my chip runs, PR 34)
READINGS = load("tests", "benchmark", "olmo_hybrid_chip_readings.json")


# -- the configuration ---------------------------------------------------------

def test_every_published_key_is_held_and_only_the_two_cuts_differ():
    published = CONFIG["source_config"]
    assert CONFIG["reduced"] == list(REDUCED)
    for key, value in published.items():
        if key in REDUCED:
            assert CONFIG[key] == REDUCED[key] != value, key
        else:  # nested groups whole: layer_types is read from its start
            assert CONFIG[key] == value, key
    assert CONFIG["layer_types"][:4] == PERIOD
    assert CONFIG["deployment"]["published"] == {
        k: published[k] for k in REDUCED}
    assert CONFIG["vocab_size"] * CONFIG["deployment"][
        "chips_sharing_the_vocabulary"] == published["vocab_size"]
    # no expert, no key of an expert's: this repo's two keys for what the
    # source's config leaves to the modelling code, as train_lm.arch_of reads
    assert CONFIG["share"] == {"norm_at": "output", "qk_norm": True}
    assert not [k for k in CONFIG if k.startswith(("moe_", "num_experts"))]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"


def test_the_source_config_is_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["source_config"] == row["config"]


def test_no_width_is_cut_and_the_floors_are_kept():
    published = CONFIG["source_config"]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "linear_num_key_heads",
                "linear_num_value_heads", "linear_key_head_dim",
                "linear_value_head_dim", "linear_conv_kernel_dim",
                "linear_allow_neg_eigval", "rms_norm_eps",
                "rope_parameters"):
        assert CONFIG[key] == published[key], key
    kinds = CONFIG["layer_types"][:CONFIG["num_hidden_layers"]]
    # one whole period, 3 : 1 as the published 24 : 8
    assert kinds == PERIOD
    assert (published["layer_types"].count("linear_attention"),
            published["layer_types"].count("full_attention")) == (24, 8)
    assert published["layer_types"] == PERIOD * 8
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]


def test_the_job_is_the_issues_and_the_assumptions_are_stated():
    train = CONFIG["train_config"]
    assert (train["optimizer"], train["lr"], train["lr_schedule"],
            train["warmup_steps"], train["weight_decay"]) == (
        "adamw", 3e-4, "warmup-cosine", 100, 1e-4)
    assert train["remat"] is True and train["attn_impl"] == "flash"
    assert train["seq_len"] == 8192
    assert JOB["train_config"] == {"algo": "sync", "prefetch": 2}
    assert JOB["per_chip_batch"] == 1 and JOB["total_updates"] == 10000
    assert JOB["data"] == {"kind": "tokens", "pool": 512, "epoch_repeats": 64}
    assert JOB["loss_must_fall"] is True and JOB["trace_seconds"] == 4.0
    assert JOB["units_per_interval"] == 1
    assert JOB["driver"] == "train_lm_ref:run"
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": NAME, "traffic": "sync_b1_t8192",
                    "chips": 1, "why": JOB["why"]}
    assert "packed 32k" in cell["why"] and "8,192" in cell["why"]
    assumed = " ".join(CONFIG["assumed"])
    for word in ("OUTPUT", "QK norm", "no rotary", "chunk 64", "WITHOUT bias",
                 "dt_bias", "A uniform in [1, 16]", "adamw", "2412.06464",
                 "2501.00656"):
        assert word in assumed, word
    assert "step by step" in " ".join(CONFIG["departures"])
    compare = CONFIG["comparison"]
    assert "experts_held_key" not in compare
    assert compare["reference"] == "reference_olmo_hybrid"
    assert compare["kernel_shapes"] == "delta_kernels:kernel_shapes"
    assert compare["counters"] == ["delta_chunk_log_decay_min"]
    for limit in ("loss_rtol", "grad_rtol", "move_rtol"):
        assert limit in compare["limits_why"], limit
    # its own metrics, found by name, name its cell alone
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL], name


def test_the_parameter_table_is_the_models():
    import jax
    import jax.numpy as jnp

    from benchmark.drivers.train_lm import arch_of
    from mpit_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab_size=CONFIG["vocab_size"],
                          arch=arch_of(CONFIG))
    tree = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 128), jnp.int32))["params"]
    size = lambda t: sum(int(np.prod(l.shape)) for l in jax.tree.leaves(t))
    named = lambda block, names: sum(
        size(v) for k, v in block.items() if k in names)
    table = CONFIG["parameters"]
    assert (size(tree["Embed_0"]) + size(tree["head"])
            + size(tree["final_norm"])) == table[
        "embedding_head_and_final_norm"] == 96341760
    groups = CONFIG["comparison"]["leaf_groups"]
    for l, kind in enumerate(PERIOD):
        block = tree[f"Block_{l}"]
        assert named(block, groups["mlp"]) == table["swiglu"] == 126812160
        if kind == "linear_attention":
            assert size(block) == table["linear_attention_layer"]
            assert named(block, groups["linattn"] + ["gate_norm"]) == table[
                "linear_attention_mixer"] == 88750332
        else:
            assert size(block) == table["full_attention_layer"]
            assert named(block, groups["attention"] + [
                "q_norm", "k_norm"]) == table["full_attention_mixer"]
    assert table["linear_attention_layer"] == 88750332 + 126812160 + 2 * 3840
    assert table["full_attention_layer"] == 58990080 + 126812160 + 2 * 3840
    assert table["one_period"] == (3 * table["linear_attention_layer"]
                                   + table["full_attention_layer"])
    assert size(tree) == table["held"] == 928862196  # 928.86M
    published = CONFIG["source_config"]
    assert table["published_whole_model"] == (
        8 * table["one_period"] + 2 * published["vocab_size"] * 3840 + 3840)
    assert round(table["published_whole_model"] / 1e9, 2) == 7.43
    assert tree["Block_0"]["lin_q"].shape == (3840, 30 * 96)
    assert tree["Block_0"]["lin_v"].shape == (3840, 30 * 192)
    assert tree["Block_0"]["conv_v"].shape == (30 * 192, 4)
    assert tree["Block_0"]["lin_a"].shape == (3840, 30)
    assert tree["Block_0"]["gate_norm"].shape == (192,)
    assert tree["Block_3"]["wq"].shape == (3840, 30 * 128)
    assert tree["Block_3"]["k_norm"].shape == (3840,)
    assert tree["Block_3"]["w_up"].shape == (3840, 11008)
    assert "Block_4" not in tree
    # every leaf is in one group of the comparison
    from benchmark.drivers import train_lm_ref

    group = train_lm_ref.grouping(groups)
    seen = {group(jax.tree_util.keystr(path)) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert seen == set(groups) == {"norms", "linattn", "attention", "mlp",
                                   "embedding", "head"}


# -- the two reference files ---------------------------------------------------

def test_the_benchmarks_reference_is_the_programs():
    """The same text below the copy's own first paragraph, and the same
    numbers on a seed."""
    import jax

    from benchmark.lib import reference_olmo_hybrid as copy
    from mpit_tpu.models import reference_olmo_hybrid as original
    from mpit_tpu.models.transformer import TransformerLM

    with open(original.__file__) as f:
        text = f.read()
    with open(copy.__file__) as f:
        assert f.read().endswith(text[3:])
    arch = JOB["rehearsal"]["train_config"]["arch"]
    tokens = jax.random.randint(jax.random.key(5), (2, 32), 0, 257)
    params = jax.jit(TransformerLM(vocab_size=257, arch=arch).init)(
        jax.random.key(6), tokens)["params"]
    both = [jax.jit(lambda p, m=m: m.loss_and_grad(
        p, tokens, tokens, arch))(params) for m in (original, copy)]
    for a, b in zip(*(jax.tree.leaves(x) for x in both)):
        np.testing.assert_array_equal(a, b)


# -- the recurrence's operations and bytes --------------------------------------

SHAPE = {"batch": 1, "t": 8192, "layers": 3, "heads": 30, "key_dim": 96,
         "value_dim": 192, "itemsize": 2}


def test_recurrence_flops_and_bytes_by_hand():
    assert delta_kernels.flops("fwd", SHAPE) == 3 * 2 * 8192 * 30 * 96 * 192
    assert delta_kernels.flops("bwd", SHAPE) == 2 * delta_kernels.flops(
        "fwd", SHAPE)
    qk, v, scalar = 8192 * 30 * 96 * 2, 8192 * 30 * 192 * 2, 8192 * 30 * 4
    assert delta_kernels.bytes_moved("fwd", SHAPE) == (
        2 * qk + 2 * v + 2 * scalar)  # q, k, v, g, beta -> o
    assert delta_kernels.bytes_moved("bwd", SHAPE) == (
        4 * qk + 3 * v + 4 * scalar)  # those and do -> five cotangents
    assert delta_kernels.bytes_moved("fwd", SHAPE) == pytest.approx(
        285.1e6, rel=1e-3)
    assert delta_kernels.bytes_moved("bwd", SHAPE) == pytest.approx(
        475.8e6, rel=1e-3)
    # bound by bytes on the v5e, forward and backward: 0.348 and 0.581 ms
    # (the products alone 0.138 ms forward)
    fwd = delta_kernels.least_seconds("fwd", SHAPE, "TPU v5 lite")
    bwd = delta_kernels.least_seconds("bwd", SHAPE, "TPU v5 lite")
    assert fwd == delta_kernels.bytes_moved("fwd", SHAPE) / 819e9
    assert delta_kernels.flops("fwd", SHAPE) / 197e12 == pytest.approx(
        0.138e-3, rel=0.01)
    assert fwd == pytest.approx(0.348e-3, rel=0.01)
    assert bwd == pytest.approx(0.581e-3, rel=0.01)
    assert delta_kernels.least_seconds_unit(SHAPE, "TPU v5 lite") == (
        pytest.approx(3 * (fwd + bwd)))
    assert 1e3 * delta_kernels.least_seconds_unit(
        SHAPE, "TPU v5 lite") == pytest.approx(2.79, rel=0.01)
    with pytest.raises(KeyError):
        delta_kernels.least_seconds("fwd", SHAPE, "no such chip")


# -- the reduction, on planes built by hand ------------------------------------

STEP = "jit(train_step)/shard_map/jvp(TransformerLM)/Block_0"
BACK = "jit(train_step)/shard_map/transpose(jvp(TransformerLM))/Block_0"
OP_NAMES = {
    "fusion.1": f"{STEP}/linattn/dot_general",
    "fusion.2": f"{STEP}/linattn/linattn_conv/mul",
    "fusion.3": f"{STEP}/linattn/delta_rule/exp",
    "while.1": f"{STEP}/linattn/delta_rule/checkpoint/while",
    "fusion.4": f"{BACK}/linattn/delta_rule/dot_general",
    "fusion.5": f"{STEP}/linattn/linattn_gate/rsqrt",
    "fusion.6": f"{STEP.replace('Block_0', 'Block_3')}/attn_proj/qk_norm/mul",
    "fusion.7": f"{STEP}/head/dot_general",
}


def hand_made_plane():
    ops, at = [], 0.0
    for unit in range(2):
        for name, ms in (("fusion.1", 6), ("fusion.2", 2), ("fusion.3", 5),
                         ("while.1", 1), ("fusion.4", 9), ("fusion.5", 3),
                         ("fusion.6", 4), ("fusion.7", 10)):
            ops.append((f"%{name} = f32[8] fusion(...)", at, ms * 1e6))
            at += ms * 1e6
    half = at / 2
    return {"XLA Modules": [("jit_train_step", 0.0, half),
                            ("jit_train_step", half, half)],
            "XLA Ops": ops}


def test_time_is_counted_under_every_scope_on_the_path():
    out = scope_spans.reduce(hand_made_plane(), OP_NAMES, delta_kernels.SCOPES)
    assert out["units"] == 2
    assert out["under_ms_unit"] == pytest.approx({
        "linattn": 6 + 2 + 5 + 1 + 9 + 3, "linattn_conv": 2,
        "delta_rule": 5 + 1 + 9, "linattn_gate": 3})
    assert out["scopes_in_program"] == [
        "delta_rule", "linattn", "linattn_conv", "linattn_gate"]
    # a program without the scopes: nothing under them, and no error
    bare = scope_spans.reduce(hand_made_plane(), {
        k: v.replace("/linattn", "").replace("/delta_rule", "")
        for k, v in OP_NAMES.items()}, delta_kernels.SCOPES)
    assert bare["scopes_in_program"] == [] == list(bare["under_ms_unit"])


def test_readers_on_a_fixture_run(monkeypatch):
    """Every new reader returns a number from a run that holds the trace,
    the counter and the recurrence's shape, and None from one that does
    not."""
    reduced = scope_spans.reduce(hand_made_plane(), OP_NAMES,
                                 delta_kernels.SCOPES)
    monkeypatch.setattr(scope_spans, "traced", lambda name: reduced)
    run = {"trace": {"busy_s": 1.0}, "device_kind": "TPU v5 lite",
           "counters": {"delta_chunk_log_decay_min": [-31.5, -40.25, -12.0]},
           "kernels": {"delta": SHAPE}}
    read = lambda name, r: importlib.import_module(
        f"benchmark.readers.{name}").read(r)
    assert read("linattn_ms_unit", run) == pytest.approx(26)
    assert read("delta_ms_unit", run) == pytest.approx(15)
    assert read("delta_chunk_log_decay_min", run) == -40.25
    least_ms = 1e3 * delta_kernels.least_seconds_unit(SHAPE, "TPU v5 lite")
    assert read("delta_roofline_pct", run) == pytest.approx(
        100 * least_ms / 15)
    assert 0 < read("delta_roofline_pct", run) < 100
    assert read("delta_roofline_pct", {**run, "kernels": {}}) is None
    for name in NEW:  # an accepted cell's run: nothing to read, no error
        assert read(name, {"trace": None}) is None
    # the parent's program under these files: a trace, none of the scopes
    monkeypatch.setattr(scope_spans, "traced", lambda name: {
        "units": 2, "under_ms_unit": {}, "scopes_in_program": []})
    for name in NEW[:3]:
        assert read(name, {**run, "counters": {}}) is None
    for name in NEW:  # each metric's file names its reader and the cell
        own = load("benchmark", "layer_metrics", f"{name}.json")
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert own["reader"] == f"{name}:read" and own["cells"] == [CELL]
        assert {k: own[k] for k in ("unit", "better", "source", "layer",
                                    "moves")} == {
            k: entry[k] for k in ("unit", "better", "source", "layer",
                                  "moves")}


def test_the_readers_on_the_chips_recorded_line():
    """The traced line a chip run printed (recorded with its ``delta_spans``
    detail): the four new metrics are what the readers make of that run."""
    recorded = READINGS["traced"]
    metrics, spans = recorded["metrics"], recorded["delta_spans"]
    assert set(NEW) | set(UNLISTED) <= set(metrics)
    assert metrics["linattn_ms_unit"]["value"] == pytest.approx(
        spans["under_ms_unit"]["linattn"])
    assert metrics["delta_ms_unit"]["value"] == pytest.approx(
        spans["under_ms_unit"]["delta_rule"])
    least_ms = 1e3 * delta_kernels.least_seconds_unit(SHAPE, "TPU v5 lite")
    assert metrics["delta_roofline_pct"]["value"] == pytest.approx(
        100 * least_ms / spans["under_ms_unit"]["delta_rule"])
    assert 0 < metrics["delta_roofline_pct"]["value"] < 105
    assert metrics["delta_chunk_log_decay_min"]["value"] < 0
    assert spans["under_ms_unit"]["linattn"] > spans["under_ms_unit"][
        "delta_rule"] > 0
    assert metrics["mfu_pct"]["value"] > 0
    assert recorded["device"]["memory_peak_bytes"] >= 0.25 * 16909336064


def test_the_readers_give_the_recorded_line_to_the_last_digit(monkeypatch):
    """The recorded ``delta_spans`` detail through the one scope reduction,
    under the family the readers name: the very numbers the chip's line
    printed, not near them."""
    recorded = READINGS["traced"]
    monkeypatch.setattr(scope_spans, "traced", lambda name: (
        recorded["delta_spans"] if name == "delta_kernels" else None))
    run = {"trace": {"busy_s": recorded["device"]["busy_s"]},
           "device_kind": recorded["device"]["kind"],
           "kernels": {"delta": SHAPE}}
    for name in NEW[:3]:
        read = importlib.import_module(f"benchmark.readers.{name}").read
        assert read(run) == recorded["metrics"][name]["value"], name


def test_the_drivers_shapes_are_the_configurations():
    """Through the function the configuration's comparison block names."""
    from benchmark.drivers import train_lm, train_lm_ref

    compare = CONFIG["comparison"]
    shapes = train_lm_ref.kernel_shapes(
        compare, train_lm.arch_of(CONFIG), 1, 8192)
    assert shapes == {"delta": SHAPE}  # the causal kernels have no reader here
    assert train_lm_ref.kernel_shapes(
        compare, {"num_hidden_layers": 2}, 1, 64) == {}


# -- what decides correct, on readings taken on the chip ------------------------

def limits():
    from benchmark.drivers import train_lm

    return {**train_lm.LIMITS, **CONFIG["comparison"]["limits"]}


#: the controls whose LOSS is past the configuration's own limit, by seed: the
#: loss of a fault swings with the seed (float8 5.6e-5 to 4.9e-4), so only
#: the gradient and the move refuse every fault at every seed
LOSS_REFUSES = {"3200000089": {"float8", "state_not_carried"},
                "3900000053": {"state_not_carried", "no_delta_term"},
                "2147483801": {"state_not_carried", "no_delta_term"}}
CONTROLS = [(seed, fault) for seed in sorted(READINGS["controls"])
            for fault in sorted(READINGS["controls"][seed])]


@pytest.mark.parametrize("seed,fault", CONTROLS,
                         ids=[f"{f}-{s}" for s, f in CONTROLS])
def test_decide_refuses_the_controls_read_on_the_chip(seed, fault):
    from benchmark.drivers import train_lm

    control = READINGS["controls"][seed][fault]
    checks = train_lm.decide({**READINGS["sound"], **control["read"]},
                             limits(), True)
    failing = {"gradient_by_group", "first_units_move"} | (
        {"first_unit_loss"} if fault in LOSS_REFUSES[seed] else set())
    assert {k for k, ok in checks.items() if not ok} == failing
    # what the chip's own decide printed, where it ran under these limits
    assert set(control.get("failed_checks", failing)) == failing
    assert (control["loss_rel_err"] > limits()["loss_rtol"]) == (
        fault in LOSS_REFUSES[seed])


@pytest.mark.parametrize("name", [
    "sound", "state_unchanged", "loss_rose", "compiled_in_the_window",
    "loss_off"])
def test_decide_on_chip_readings(name):
    from benchmark.drivers import train_lm

    sound = READINGS["sound"]
    off = sound["reference_loss"] * (1 + 2 * limits()["loss_rtol"])
    change, failing = {
        "sound": ({}, set()),
        "state_unchanged": ({"move_rel_err": 1.0, "move_norm": 0.0},
                            {"first_units_move"}),
        "loss_rose": ({"loss_fell": False}, {"loss_fell"}),
        "compiled_in_the_window": ({"compiled_in_window": 1},
                                   {"nothing_compiled_in_window"}),
        # 3e-4 of the reference: inside the accepted cells' 2e-3, past this
        # configuration's own limit
        "loss_off": ({"first_losses": [off, off]}, {"first_unit_loss"}),
    }[name]
    checks = train_lm.decide({**sound, **change}, limits(), True)
    assert {k for k, ok in checks.items() if not ok} == failing


def test_the_three_controls_are_the_issues():
    from benchmark.drivers import train_lm

    for by_fault in READINGS["controls"].values():
        assert sorted(by_fault) == [
            "float8", "no_delta_term", "state_not_carried"]
    assert limits()["loss_rtol"] == 1.5e-4 < train_lm.LIMITS["loss_rtol"]
    # the routing reads of a model without experts are vacuous and pass
    sound = READINGS["sound"]
    assert (sound["rows_held"], sound["rows_dropped"],
            sound["routing_mismatch"]) == ([], 0.0, 0.0)


# -- the cell's traced rehearsal -----------------------------------------------

def test_the_new_cells_traced_rehearsal():
    """Through ``run_cell.py --rehearsal --trace 1``: correct, every accepted
    reader gives a number or is left out, and the counter's metric reads."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, "benchmark/run_cell.py", "--workload", CELL,
         "--seed", "2147483659", "--seconds", "2", "--trace", "1",
         "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    line = lines[-1]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) <= set(UNLISTED) | set(NEW)
    assert {"compile_s", "unit_ms_p50", "input_path_ms_unit",
            "dispatch_host_ms_unit", "init_state_s", "input_host_ms_unit",
            "delta_chunk_log_decay_min"} <= set(line["metrics"])
    assert line["metrics"]["delta_chunk_log_decay_min"]["value"] < 0
    checks = next(l["value"] for l in lines if l.get("detail") == "checks")
    read = checks["read"]
    assert (read["rows_held"], read["rows_dropped"], read["rows_expected"],
            read["routing_mismatch"]) == ([], 0.0, 0.0, 0.0)
    assert set(read["grad_rel_err_by_group"]) == set(
        CONFIG["comparison"]["leaf_groups"])
    assert 0 < read["grad_rel_err_by_group"]["linattn"]
    # the published limits stand in the configuration; the rehearsal widens
    assert checks["limits"]["grad_rtol"] == 0.4
    assert checks["limits"]["rows_held_band"]  # decide's, passed as written
