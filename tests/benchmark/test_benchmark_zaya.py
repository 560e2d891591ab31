"""What the ZAYA1-8B configuration adds to the benchmark: the configuration's
file against the catalog's row, the parameter recount, the entries by rule,
the benchmark's copy of the reference against the program's, the mixing's
operation and byte counts against counts by hand, the readers on planes built
by hand and on the chip's recorded line, ``decide`` on readings from the
chip, and the new cell's traced rehearsal. CPU."""

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

from benchmark.lib import cca_kernels, scope_spans  # noqa: E402

CELL = "zaya1_sync_1chip_8k"
NAME = "zaya1-8b"
#: the model catalog (a JSON-lines file, one architecture a row), if given
CATALOG = os.environ.get("MODEL_CATALOG", "")
NEW = ["cca_mix_ms_unit", "cca_mix_roofline_pct", "router_mlp_ms_unit"]
REDUCED = {"num_hidden_layers": 6, "num_experts": 8, "vocab_size": 32784}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = load("benchmark", "configs", f"{NAME}.json")
MANIFEST = load("BENCHMARK.json")
JOB = load("benchmark", "workloads", f"{CELL}.json")
#: a sound run's readings at the published widths, the controls' and a
#: traced run's line (chip runs)
READINGS = load("tests", "benchmark", "zaya_chip_readings.json")


# -- the configuration ---------------------------------------------------------

def test_every_published_key_is_held_and_only_the_three_cuts_differ():
    published = CONFIG["source_config"]
    assert CONFIG["reduced"] == list(REDUCED)
    for key, value in published.items():
        if key in REDUCED:
            assert CONFIG[key] == REDUCED[key] != value, key
        else:  # nested groups whole: layer_types is read from its start
            assert CONFIG[key] == value, key
    assert CONFIG["deployment"]["published"] == {
        k: published[k] for k in REDUCED}
    # 2 chips share each layer: 8 of 16 experts here
    share = CONFIG["share"]
    assert share["num_routed_experts"] == published["num_experts"] == 16
    assert CONFIG["num_experts"] * CONFIG["deployment"][
        "chips_sharing_each_layer"] == published["num_experts"]
    assert share["expert_offset"] == 0 and share["moe_routing_no_grad"]
    assert share["router_aux_loss_coef"] == 0.001
    # every token's one choice fits the dispatch buffer: nothing is dropped
    assert share["moe_row_bound"] == CONFIG["train_config"]["seq_len"] * (
        published["num_experts_per_tok"])
    assert CONFIG["vocab_size"] * CONFIG["deployment"][
        "chips_sharing_the_vocabulary"] == published["vocab_size"]


def test_the_source_config_is_the_catalog_row():
    if not CATALOG or not os.path.exists(CATALOG):
        pytest.skip("MODEL_CATALOG names no model catalog")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["source_config"] == row["config"]


def test_no_width_is_cut_and_the_floors_are_kept():
    published = CONFIG["source_config"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "cca_time0", "cca_time1", "router_hidden_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "partial_rotary_factor", "rope_parameters", "rms_norm_eps",
                "tie_word_embeddings"):
        assert CONFIG[key] == published[key], key
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["head_dim"],
            CONFIG["router_hidden_size"], CONFIG["moe_intermediate_size"],
            CONFIG["num_experts_per_tok"]) == (2048, 8, 2, 128, 256, 2048, 1)
    assert CONFIG["rope_parameters"]["hybrid"]["rope_theta"] == 5000000
    # a period is one layer; at least four layers, 8 experts and 1/8 of
    # the vocabulary
    assert set(published["layer_types"]) == {"hybrid"}
    assert CONFIG["num_hidden_layers"] >= 4 and CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]


def test_the_job_and_the_assumptions_are_stated():
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
    assert JOB["units_per_interval"] in (1, 2)
    assert JOB["driver"] == "train_lm_ref:run"
    assumed = " ".join(CONFIG["assumed"])
    for word in ("2510.04476", "value shift", "no bias", "q-k mean",
                 "temperature", "exact GELU", "gamma", "from 0.5",
                 "not renormalised", "2101.03961", "residual scaling",
                 "moe_routing_no_grad", "router_aux_loss_coef 0.001",
                 "moe_row_bound 8192", "adamw"):
        assert word in assumed, word
    departures = " ".join(CONFIG["departures"])
    for word in ("bfloat16", "balancing bias", "hybrid_sliding"):
        assert word in departures, word
    compare = CONFIG["comparison"]
    assert compare["reference"] == "reference_zaya"
    assert compare["kernel_shapes"] == "cca_kernels:kernel_shapes"
    assert compare["experts_held_key"] == "num_experts"
    assert compare["counters"] == [
        "moe_rows_held", "moe_load_max_over_mean", "moe_rows_dropped"]
    assert set(compare["leaf_groups"]) == {
        "norms", "cca_proj", "cca_conv", "router", "experts", "embed"}
    for limit in compare["limits"]:
        assert limit in compare["limits_why"], limit


# -- the entries, by rule ----------------------------------------------------------

def test_the_manifest_gains_one_configuration_one_cell_and_three_metrics():
    config = [c for c in MANIFEST["configs"] if c["name"] == NAME]
    assert len(config) == 1
    assert config[0] == {
        "name": NAME, "source": CONFIG["source"],
        "file": f"benchmark/configs/{NAME}.json",
        "reduced": CONFIG["reduced"], "why": config[0]["why"]}
    assert "CCA" in config[0]["why"] and "top-1-of-16" in config[0]["why"]
    cells = [w for w in MANIFEST["workloads"] if w["config"] == NAME]
    assert cells == [{"name": CELL, "config": NAME,
                      "traffic": "sync_b1_t8192", "chips": 1,
                      "why": JOB["why"]}]
    assert "8,192" in JOB["why"] and "1/2" in JOB["why"]
    listed = [m for m in MANIFEST["per_layer"]
              if CELL in m.get("workloads", ())]
    assert sorted(m["name"] for m in listed) == sorted(NEW)
    for m in listed:
        assert m["workloads"] == [CELL] and m["source"] == "device_trace"
        assert m["moves"] == "samples_per_s_chip"
        own = load("benchmark", "layer_metrics", f"{m['name']}.json")
        assert own["reader"] == f"{m['name']}:read" and own["cells"] == [CELL]
        assert {k: own[k] for k in ("unit", "better", "source", "layer",
                                    "moves")} == {
            k: m[k] for k in ("unit", "better", "source", "layer", "moves")}


def test_the_parameter_table_is_the_models():
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import train_lm, train_lm_ref
    from mpit_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab_size=CONFIG["vocab_size"],
                          arch=train_lm.arch_of(CONFIG))
    tree = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 128), jnp.int32))["params"]
    size = lambda t: sum(int(np.prod(l.shape)) for l in jax.tree.leaves(t))
    groups = CONFIG["comparison"]["leaf_groups"]
    named = lambda block, group: sum(size(v) for k, v in block.items()
                                     if k in groups[group])
    table = CONFIG["parameters"]
    for l in range(6):
        block = tree[f"Block_{l}"]
        assert size(block) == table["layer_with_8_held"] == 106908169
        assert named(block, "cca_proj") == table["cca_projections"] == (
            2048 * 1024 + 2048 * 256 + 2 * 2048 * 128 + 1024 * 2048)
        assert named(block, "cca_conv") == table["cca_convolutions"] == (
            1280 * 2 + 10 * 128 * 128 * 2)
        assert named(block, "router") == table["router"] == (
            2048 * 256 + 2 * 256 * 256 + 256 * 16 + 1)
        assert named(block, "norms") == table[
            "norms_scales_and_temperature"] == 6 * 2048 + 8
        assert named(block, "experts") == table["experts_held_a_layer"] == (
            8 * table["routed_expert"]) == 8 * 3 * 2048 * 2048
    assert "Block_6" not in tree and "head" not in tree  # six layers, tied
    assert size(tree["Embed_0"]) + size(tree["final_norm"]) == table[
        "embedding_slice_and_final_norm"] == 32784 * 2048 + 2048
    assert table["six_layers"] == 6 * table["layer_with_8_held"]
    assert size(tree) == table["held"] == 708592694
    assert 16 * table["held"] / 16909336064 == pytest.approx(0.6705, abs=1e-4)
    assert table["layer_with_all_16"] == (
        table["layer_with_8_held"] + table["experts_held_a_layer"])
    published = CONFIG["source_config"]
    assert table["published_whole_model"] == (
        40 * table["layer_with_all_16"] + published["vocab_size"] * 2048
        + 2048)
    assert tree["Block_0"]["cca_conv1"].shape == (10, 128, 128, 2)
    assert tree["Block_0"]["router_w3"].shape == (256, 16)
    assert tree["Block_0"]["moe_w_down"].shape == (8, 2048, 2048)
    group = train_lm_ref.grouping(groups)
    seen = {group(jax.tree_util.keystr(path)) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert seen == set(groups)


# -- the two reference files ---------------------------------------------------

def test_the_benchmarks_reference_is_the_programs():
    """The same text below the copy's own first paragraph, and the same
    numbers on a seed."""
    import jax

    from benchmark.lib import reference_zaya as copy
    from mpit_tpu.models import reference_zaya as original
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


# -- the mixing's operations and bytes --------------------------------------------

SHAPE = {"batch": 1, "t": 8192, "layers": 6, "heads": 8, "kv_heads": 2,
         "d": 128, "time0": 2, "time1": 2, "itemsize": 2}


def test_mixing_flops_and_bytes_by_hand():
    depthwise = 2 * 2 * 8192 * 1280
    per_head = 2 * 2 * 8192 * 10 * 128 * 128
    assert cca_kernels.flops("fwd", SHAPE) == depthwise + per_head
    assert cca_kernels.flops("bwd", SHAPE) == 2 * (depthwise + per_head)
    q0, k0, v_halves = 8192 * 1024 * 2, 8192 * 256 * 2, 2 * 8192 * 128 * 2
    taps = (1280 * 2 + 10 * 128 * 128 * 2) * 2
    q, k, v = 8192 * 1024 * 2, 8192 * 256 * 2, 8192 * 256 * 2
    operands = q0 + k0 + v_halves + taps
    assert cca_kernels.bytes_moved("fwd", SHAPE) == operands + q + k + v
    assert cca_kernels.bytes_moved("bwd", SHAPE) == 2 * operands + q + k + v
    assert cca_kernels.bytes_moved("fwd", SHAPE) == pytest.approx(
        50.99e6, rel=1e-3)
    # bound by bytes on the v5e both ways: 0.0623 and 0.0935 ms a layer
    fwd = cca_kernels.least_seconds("fwd", SHAPE, "TPU v5 lite")
    bwd = cca_kernels.least_seconds("bwd", SHAPE, "TPU v5 lite")
    assert fwd == cca_kernels.bytes_moved("fwd", SHAPE) / 819e9
    assert bwd == cca_kernels.bytes_moved("bwd", SHAPE) / 819e9
    assert cca_kernels.flops("fwd", SHAPE) / 197e12 < fwd
    assert 1e3 * cca_kernels.least_seconds_unit(
        SHAPE, "TPU v5 lite") == pytest.approx(6 * 1e3 * (fwd + bwd))
    assert 1e3 * cca_kernels.least_seconds_unit(
        SHAPE, "TPU v5 lite") == pytest.approx(0.934, rel=0.01)
    with pytest.raises(KeyError):
        cca_kernels.least_seconds("fwd", SHAPE, "no such chip")


def test_the_drivers_shapes_are_the_configurations():
    """Through the function the configuration's comparison block names."""
    from benchmark.drivers import train_lm, train_lm_ref

    shapes = train_lm_ref.kernel_shapes(
        CONFIG["comparison"], train_lm.arch_of(CONFIG), 1, 8192)
    assert shapes["cca_mix"] == SHAPE
    assert shapes["flash_causal"] == {
        "batch": 1, "heads": 8, "kv_heads": 2, "t": 8192, "d": 128,
        "window": None, "itemsize": 2, "layers": 6}


# -- the reduction, on planes built by hand ------------------------------------

STEP = "jit(train_step)/shard_map/jvp(TransformerLM)/Block_0"
BACK = "jit(train_step)/shard_map/transpose(jvp(TransformerLM))/Block_0"
OP_NAMES = {
    "fusion.1": f"{STEP}/attn_proj/dot_general",
    "fusion.2": f"{STEP}/attn_proj/cca_mix/mul",
    "fusion.3": f"{BACK}/attn_proj/cca_mix/dot_general",
    "fusion.4": f"{STEP}/attn_proj/rope/mul",
    "fusion.5": f"{STEP}/mlp/moe_router/router_mlp/dot_general",
    "fusion.6": f"{BACK}/mlp/moe_router/router_mlp/erf",
    "fusion.7": f"{STEP}/mlp/moe_experts/ragged_dot",
    "fusion.8": f"{STEP}/head/dot_general",
}


def hand_made_plane():
    ops, at = [], 0.0
    for unit in range(2):
        for name, ms in (("fusion.1", 6), ("fusion.2", 2), ("fusion.3", 5),
                         ("fusion.4", 1), ("fusion.5", 3), ("fusion.6", 4),
                         ("fusion.7", 9), ("fusion.8", 10)):
            ops.append((f"%{name} = f32[8] fusion(...)", at, ms * 1e6))
            at += ms * 1e6
    half = at / 2
    return {"XLA Modules": [("jit_train_step", 0.0, half),
                            ("jit_train_step", half, half)],
            "XLA Ops": ops}


def test_time_is_counted_under_every_scope_on_the_path():
    out = scope_spans.reduce(hand_made_plane(), OP_NAMES, cca_kernels.SCOPES)
    assert out["units"] == 2
    assert out["under_ms_unit"] == pytest.approx(
        {"cca_mix": 2 + 5, "router_mlp": 3 + 4})
    assert out["scopes_in_program"] == ["cca_mix", "router_mlp"]
    # the parent's program: nothing under the scopes, and no error
    bare = scope_spans.reduce(hand_made_plane(), {
        k: v.replace("/cca_mix", "").replace("/router_mlp", "")
        for k, v in OP_NAMES.items()}, cca_kernels.SCOPES)
    assert bare["scopes_in_program"] == [] == list(bare["under_ms_unit"])


def read(name, run):
    return importlib.import_module(f"benchmark.readers.{name}").read(run)


def test_readers_on_a_fixture_run(monkeypatch):
    """Every new reader returns a number from a run that holds the trace and
    the mixing's shape, and None from one that does not."""
    reduced = scope_spans.reduce(hand_made_plane(), OP_NAMES,
                                 cca_kernels.SCOPES)
    monkeypatch.setattr(scope_spans, "traced", lambda name: reduced)
    run = {"trace": {"busy_s": 1.0}, "device_kind": "TPU v5 lite",
           "kernels": {"cca_mix": SHAPE}}
    assert read("cca_mix_ms_unit", run) == pytest.approx(7)
    assert read("router_mlp_ms_unit", run) == pytest.approx(7)
    least_ms = 1e3 * cca_kernels.least_seconds_unit(SHAPE, "TPU v5 lite")
    assert read("cca_mix_roofline_pct", run) == pytest.approx(
        100 * least_ms / 7)
    assert read("cca_mix_roofline_pct", {**run, "kernels": {}}) is None
    for name in NEW:  # an accepted cell's untraced run: no error
        assert read(name, {"trace": None}) is None
    monkeypatch.setattr(scope_spans, "traced", lambda name: {
        "units": 2, "under_ms_unit": {}, "scopes_in_program": []})
    for name in NEW:  # the parent's program under these files
        assert read(name, run) is None


def test_the_readers_on_the_chips_recorded_line(monkeypatch):
    """The traced line a chip run printed, with its ``cca_spans`` detail:
    the three new metrics are what the readers make of that run, to the last
    digit, and every unlisted metric is in the line."""
    recorded = READINGS["traced"]
    metrics, spans = recorded["metrics"], recorded["cca_spans"]
    unlisted = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m}
    assert set(NEW) | unlisted <= set(metrics)
    monkeypatch.setattr(scope_spans, "traced", lambda name: (
        spans if name == "cca_kernels" else None))
    run = {"trace": {"busy_s": recorded["device"]["busy_s"]},
           "device_kind": recorded["device"]["kind"],
           "kernels": {"cca_mix": SHAPE}}
    for name in NEW:
        assert read(name, run) == metrics[name]["value"] > 0, name
    assert 0 < metrics["cca_mix_roofline_pct"]["value"] < 100
    assert recorded["device"]["memory_peak_bytes"] >= 0.25 * 16909336064


# -- what decides correct, on readings taken on the chip ------------------------

def limits():
    from benchmark.drivers import train_lm

    return {**train_lm.LIMITS, **CONFIG["comparison"]["limits"]}


CONTROLS = [(seed, fault) for seed in sorted(READINGS["controls"])
            for fault in sorted(READINGS["controls"][seed])]


@pytest.mark.parametrize("seed,fault", CONTROLS,
                         ids=[f"{f}-{s}" for s, f in CONTROLS])
def test_decide_refuses_the_controls_read_on_the_chip(seed, fault):
    """Each control, pushed through ``decide`` as the chip read it, is not
    correct, by the checks the chip's own ``decide`` named."""
    from benchmark.drivers import train_lm

    control = READINGS["controls"][seed][fault]
    checks = train_lm.decide({**READINGS["sound"], **control["read"]},
                             limits(), True)
    failing = {k for k, ok in checks.items() if not ok}
    assert failing and failing == set(control["failed_checks"])


@pytest.mark.parametrize("name", [
    "sound", "state_unchanged", "loss_rose", "a_row_dropped",
    "experts_left_empty"])
def test_decide_on_chip_readings(name):
    from benchmark.drivers import train_lm

    sound = READINGS["sound"]
    change, failing = {
        "sound": ({}, set()),
        "state_unchanged": ({"move_rel_err": 1.0, "move_norm": 0.0},
                            {"first_units_move"}),
        "loss_rose": ({"loss_fell": False}, {"loss_fell"}),
        "a_row_dropped": ({"rows_dropped": 1.0}, {"no_row_dropped"}),
        "experts_left_empty": ({"rows_held": sound["rows_held"] + [
            0.2 * sound["rows_expected"]]}, {"rows_held_in_band"}),
    }[name]
    checks = train_lm.decide({**sound, **change}, limits(), True)
    assert {k for k, ok in checks.items() if not ok} == failing


def test_the_controls_are_the_four_faults_at_two_seeds():
    assert len(READINGS["controls"]) >= 2
    for by_fault in READINGS["controls"].values():
        assert sorted(by_fault) == [
            "float8", "no_conv", "no_eda", "no_value_shift"]
    sound = READINGS["sound"]
    assert sound["rows_expected"] == 8192 * 1 * 8 / 16


# -- the cell's traced rehearsal -----------------------------------------------

def test_the_new_cells_traced_rehearsal():
    """Through ``run_cell.py --rehearsal --trace 1``: correct, the routing
    read and held to its band, the router's groups compared, every reader
    gives a number or is left out."""
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
    unlisted = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m}
    assert set(line["metrics"]) <= unlisted | set(NEW)
    assert {"compile_s", "unit_ms_p50", "input_path_ms_unit",
            "dispatch_host_ms_unit", "init_state_s",
            "input_host_ms_unit"} <= set(line["metrics"])
    checks = next(l["value"] for l in lines if l.get("detail") == "checks")
    read = checks["read"]
    assert read["rows_expected"] == 2 * 32 * 1 * 4 / 8
    assert read["rows_held"] and read["rows_dropped"] == 0.0
    assert set(read["grad_rel_err_by_group"]) == set(
        CONFIG["comparison"]["leaf_groups"])
    assert 0 < read["grad_rel_err_by_group"]["router"]
    assert 0 < read["grad_rel_err_by_group"]["cca_conv"]
    reference = next(l["value"] for l in lines
                     if l.get("detail") == "reference")
    assert len(reference["routing_mismatch_by_layer"]) == 2
