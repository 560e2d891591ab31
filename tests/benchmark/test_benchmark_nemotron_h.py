"""What PR 32 added to the benchmark: the configuration's file against the
catalog's row, the benchmark's copy of the reference against the program's,
the scan's operation and byte counts against counts by hand, the new reduction
on planes built by hand, the readers on a fixture, ``decide`` on readings
from the chip, and the new cell's traced rehearsal. CPU."""

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

from benchmark.lib import scope_spans, ssm_kernels  # noqa: E402

CELL = "nemotron3_nano_sync_1chip_8k"
NAME = "nemotron-3-nano-30b-a3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["ssm_ms_unit", "ssd_ms_unit", "ssd_roofline_pct",
       "ssm_chunk_log_decay_min"]
#: accepted per-layer metrics without a ``workloads`` list: the new cell's
#: traced line has to hold every one (the chip's; the CPU reads the first six)
UNLISTED = ["compile_s", "input_host_ms_unit", "unit_ms_p50", "mfu_pct",
            "device_idle_pct", "input_path_ms_unit", "dispatch_host_ms_unit",
            "init_state_s", "attention_ms_unit", "mlp_ms_unit",
            "head_loss_ms_unit", "optimizer_ms_unit", "idle_unnamed_pct"]
REDUCED = {"num_hidden_layers": 9, "n_routed_experts": 8, "vocab_size": 16384}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = load("benchmark", "configs", f"{NAME}.json")
MANIFEST = load("BENCHMARK.json")


# -- the configuration ---------------------------------------------------------

def test_every_published_key_is_held_and_only_the_three_cuts_differ():
    published = CONFIG["source_config"]
    assert CONFIG["reduced"] == list(REDUCED)
    for key, value in published.items():
        if key in REDUCED:
            assert CONFIG[key] == REDUCED[key] != value, key
        elif key == "hybrid_override_pattern":  # cut with the depth
            assert CONFIG[key] == value[:9] == "MEMEM*EME"
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["deployment"]["published"] == {
        **{k: published[k] for k in REDUCED},
        "hybrid_override_pattern": published["hybrid_override_pattern"]}
    assert CONFIG["deployment"]["chips_sharing_each_layer"] * CONFIG[
        "n_routed_experts"] == published["n_routed_experts"] == 128
    assert CONFIG["share"]["num_routed_experts"] == 128
    assert CONFIG["share"]["expert_offset"] == 0
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"


def test_the_source_config_is_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["source_config"] == row["config"]


def test_no_width_is_cut_and_the_floors_are_kept():
    published = CONFIG["source_config"]
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "mamba_num_heads", "mamba_head_dim",
                "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
                "expand", "intermediate_size", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "num_experts_per_tok",
                "routed_scaling_factor"):
        assert CONFIG[key] == published[key], key
    pattern = CONFIG["hybrid_override_pattern"]
    # one whole period: every kind of layer, in the driver's count of one
    assert len(pattern) == CONFIG["num_hidden_layers"] == 9
    assert {k: pattern.count(k) for k in "ME*"} == {"M": 4, "E": 4, "*": 1}
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]


def test_the_job_is_the_issues_and_the_assumptions_are_stated():
    train = CONFIG["train_config"]
    assert (train["optimizer"], train["lr"], train["lr_schedule"],
            train["warmup_steps"], train["weight_decay"]) == (
        "adamw", 3e-4, "warmup-cosine", 100, 1e-4)
    assert train["remat"] is True and train["attn_impl"] == "flash"
    assert train["seq_len"] == 8192
    job = load("benchmark", "workloads", f"{CELL}.json")
    assert job["train_config"] == {"algo": "sync", "prefetch": 2}
    assert job["per_chip_batch"] == 1 and job["total_updates"] == 10000
    assert job["data"] == {"kind": "tokens", "pool": 512, "epoch_repeats": 64}
    assert job["loss_must_fall"] is True and job["trace_seconds"] == 4.0
    assert job["driver"] == "train_lm_ref:run"
    compare = CONFIG["comparison"]
    assert compare["reference"] == "reference_nemotron_h"
    assert compare["kernel_shapes"] == "ssm_kernels:kernel_shapes"
    assert compare["experts_held_key"] == "n_routed_experts"
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": NAME, "traffic": "sync_b1_t8192",
                    "chips": 1, "why": job["why"]}
    assert "384 tokens" in cell["why"] and "16x" in cell["why"]
    share = CONFIG["share"]
    assert share["moe_routing_no_grad"] is True
    assert share["router_aux_loss_coef"] in (1e-4, 1e-3)
    assert share["moe_row_bound"] % (8192 * 6 * 8 // 128) == 0
    assumed = " ".join(CONFIG["assumed"])
    for word in ("no rotary", "moe_routing_no_grad", "router_aux_loss_coef",
                 "moe_row_bound", "initialisation", "adamw"):
        assert word in assumed, word
    departures = " ".join(CONFIG["departures"])
    assert "e_score_correction_bias" in departures
    assert "rescale_prenorm_residual" in departures
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
    table = CONFIG["parameters"]
    assert (size(tree["Embed_0"]) + size(tree["head"])
            + size(tree["final_norm"])) == table[
        "embedding_head_and_final_norm"]
    for l, kind in enumerate(CONFIG["hybrid_override_pattern"]):
        assert size(tree[f"Block_{l}"]) == table[{
            "M": "mamba2_layer", "*": "attention_layer",
            "E": "expert_layer_with_8_held"}[kind]], l
    assert table["expert_layer_with_8_held"] == table[
        "expert_layer_outside_routed_experts"] + 8 * table["routed_expert"]
    assert size(tree) == table["held"] == 666963456
    published = CONFIG["source_config"]
    count = lambda k: published["hybrid_override_pattern"].count(k)
    assert table["published_whole_model"] == (
        count("M") * table["mamba2_layer"]
        + count("*") * table["attention_layer"]
        + count("E") * (table["expert_layer_outside_routed_experts"]
                        + 128 * table["routed_expert"])
        + 2 * published["vocab_size"] * 2688 + 2688)
    assert tree["Block_0"]["in_proj"].shape == (2688, 4096 + 6144 + 64)
    assert tree["Block_0"]["conv_w"].shape == (6144, 4)
    assert tree["Block_1"]["moe_router"].shape == (2688, 128)
    assert tree["Block_1"]["moe_w_up"].shape == (8, 2688, 1856)
    assert tree["Block_1"]["shared_w_down"].shape == (3712, 2688)
    assert tree["Block_5"]["wq"].shape == (2688, 32 * 128)
    assert tree["Block_5"]["wk"].shape == (2688, 2 * 128)
    # every leaf is in one group of the comparison
    from benchmark.drivers import train_lm_ref

    group = train_lm_ref.grouping(CONFIG["comparison"]["leaf_groups"])
    groups = {group(jax.tree_util.keystr(path)) for path, _ in
              jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert groups == set(CONFIG["comparison"]["leaf_groups"])
    assert "ssm" in groups


# -- the two reference files ---------------------------------------------------

def test_the_benchmarks_reference_is_the_programs():
    """The same text below the copy's own first paragraph, and the same
    numbers on a seed."""
    import jax

    from benchmark.lib import reference_nemotron_h as copy
    from mpit_tpu.models import reference_nemotron_h as original
    from mpit_tpu.models.transformer import TransformerLM

    with open(original.__file__) as f:
        text = f.read()
    with open(copy.__file__) as f:
        assert f.read().endswith(text[3:])
    arch = load("benchmark", "workloads", f"{CELL}.json")[
        "rehearsal"]["train_config"]["arch"]
    tokens = jax.random.randint(jax.random.key(5), (2, 32), 0, 257)
    params = jax.jit(TransformerLM(vocab_size=257, arch=arch).init)(
        jax.random.key(6), tokens)["params"]
    share = dict(experts_held=arch["n_routed_experts"], expert_offset=0)
    both = [jax.jit(lambda p, m=m: m.loss_and_grad(
        p, tokens, tokens, arch, **share))(params) for m in (original, copy)]
    for a, b in zip(*(jax.tree.leaves(x) for x in both)):
        np.testing.assert_array_equal(a, b)


# -- the scan's operations and bytes -------------------------------------------

SHAPE = {"batch": 1, "t": 8192, "layers": 4, "heads": 64, "head_dim": 64,
         "groups": 8, "state": 128, "itemsize": 2}


def test_scan_flops_and_bytes_by_hand():
    assert ssm_kernels.flops("fwd", SHAPE) == 2 * 2 * 8192 * 64 * 64 * 128
    assert ssm_kernels.flops("bwd", SHAPE) == 2 * ssm_kernels.flops(
        "fwd", SHAPE)
    x, bc, dt = 8192 * 4096 * 2, 8192 * 8 * 128 * 2, 8192 * 64 * 4
    assert ssm_kernels.bytes_moved("fwd", SHAPE) == 2 * x + 2 * bc + dt
    assert ssm_kernels.bytes_moved("bwd", SHAPE) == 3 * x + 4 * bc + 2 * dt
    # bound by bytes on the v5e, forward and backward: 0.207 and 0.333 ms
    fwd = ssm_kernels.least_seconds("fwd", SHAPE, "TPU v5 lite")
    bwd = ssm_kernels.least_seconds("bwd", SHAPE, "TPU v5 lite")
    assert fwd == ssm_kernels.bytes_moved("fwd", SHAPE) / 819e9
    assert fwd == pytest.approx(0.207e-3, rel=0.01)
    assert bwd == pytest.approx(0.333e-3, rel=0.01)
    assert ssm_kernels.least_seconds_unit(SHAPE, "TPU v5 lite") == (
        pytest.approx(4 * (fwd + bwd)))
    with pytest.raises(KeyError):
        ssm_kernels.least_seconds("fwd", SHAPE, "no such chip")


# -- the reduction, on planes built by hand ------------------------------------

STEP = "jit(train_step)/shard_map/jvp(TransformerLM)/Block_0"
BACK = "jit(train_step)/shard_map/transpose(jvp(TransformerLM))/Block_0"
OP_NAMES = {
    "fusion.1": f"{STEP}/ssm/dot_general",
    "fusion.2": f"{STEP}/ssm/ssm_conv/mul",
    "fusion.3": f"{STEP}/ssm/ssd/exp",
    "while.1": f"{STEP}/ssm/ssd/while",
    "fusion.4": f"{BACK}/ssm/ssd/dot_general",
    "fusion.5": f"{STEP}/ssm/ssm_gate/rsqrt",
    "fusion.6": f"{STEP.replace('Block_0', 'Block_1')}/mlp/moe_router/top_k",
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
    out = scope_spans.reduce(hand_made_plane(), OP_NAMES, ssm_kernels.SCOPES)
    assert out["units"] == 2
    assert out["under_ms_unit"] == pytest.approx({
        "ssm": 6 + 2 + 5 + 1 + 9 + 3, "ssm_conv": 2, "ssd": 5 + 1 + 9,
        "ssm_gate": 3})
    assert out["scopes_in_program"] == ["ssd", "ssm", "ssm_conv", "ssm_gate"]
    # a program without the scopes: nothing under them, and no error
    bare = scope_spans.reduce(hand_made_plane(), {
        k: v.replace("/ssm", "").replace("/ssd", "") for k, v in
        OP_NAMES.items()}, ssm_kernels.SCOPES)
    assert bare["scopes_in_program"] == [] == list(bare["under_ms_unit"])


def test_readers_on_a_fixture_run(monkeypatch):
    """Every new reader returns a number from a run that holds the trace,
    the counter and the scan's shape, and None from one that does not."""
    reduced = scope_spans.reduce(hand_made_plane(), OP_NAMES,
                                 ssm_kernels.SCOPES)
    monkeypatch.setattr(scope_spans, "traced", lambda name: reduced)
    run = {"trace": {"busy_s": 1.0}, "device_kind": "TPU v5 lite",
           "counters": {"ssm_chunk_log_decay_min": [-310.5, -402.25, -120.0]},
           "kernels": {"ssd": SHAPE}}
    read = lambda name, r: importlib.import_module(
        f"benchmark.readers.{name}").read(r)
    assert read("ssm_ms_unit", run) == pytest.approx(26)
    assert read("ssd_ms_unit", run) == pytest.approx(15)
    assert read("ssm_chunk_log_decay_min", run) == -402.25
    least_ms = 1e3 * ssm_kernels.least_seconds_unit(SHAPE, "TPU v5 lite")
    assert read("ssd_roofline_pct", run) == pytest.approx(100 * least_ms / 15)
    assert 0 < read("ssd_roofline_pct", run) < 100
    assert read("ssd_roofline_pct", {**run, "kernels": {}}) is None
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


def test_the_readers_give_the_recorded_line_to_the_last_digit(monkeypatch):
    """A traced line the chip printed under the readers of ``ssm_spans.py``,
    recorded with its ``ssm_spans`` detail: the one scope reduction's readers,
    under the family they name, give the very numbers of that line."""
    recorded = SOUND["traced"]
    monkeypatch.setattr(scope_spans, "traced", lambda name: (
        recorded["ssm_spans"] if name == "ssm_kernels" else None))
    run = {"trace": {"busy_s": recorded["device"]["busy_s"]},
           "device_kind": recorded["device"]["kind"],
           "kernels": {"ssd": SHAPE}}
    for name in NEW[:3]:
        read = importlib.import_module(f"benchmark.readers.{name}").read
        assert read(run) == recorded["metrics"][name]["value"], name
    assert set(NEW) | set(UNLISTED) <= set(recorded["metrics"])
    assert recorded["device"]["memory_peak_bytes"] >= 0.25 * 16909336064


def test_the_drivers_shapes_are_the_configurations():
    """Through the function the configuration's comparison block names."""
    from benchmark.drivers import train_lm, train_lm_ref

    compare = CONFIG["comparison"]
    shapes = train_lm_ref.kernel_shapes(
        compare, train_lm.arch_of(CONFIG), 1, 8192)
    assert shapes["ssd"] == SHAPE
    assert shapes["flash_causal"] == {
        "batch": 1, "heads": 32, "kv_heads": 2, "t": 8192, "d": 128,
        "window": None, "itemsize": 2}
    assert train_lm_ref.kernel_shapes(
        compare, {"num_hidden_layers": 2}, 1, 64) == {}


# -- what decides correct, on readings taken on the chip ------------------------

#: a sound run's readings at the published widths and the three controls'
#: (my chip runs, PR 32)
SOUND = load("tests", "benchmark", "nemotron_h_chip_readings.json")


def limits():
    from benchmark.drivers import train_lm

    return {**train_lm.LIMITS, **CONFIG["comparison"]["limits"]}


@pytest.mark.parametrize("name", sorted(SOUND["controls"]) + [
    "sound", "routing_collapsed", "a_row_dropped", "state_unchanged",
    "loss_rose"])
def test_decide_on_chip_readings(name):
    from benchmark.drivers import train_lm

    sound = SOUND["sound"]
    change, failing = {
        "sound": ({}, set()),
        "routing_collapsed": ({"rows_held": [3100.0, 1500.5, 90.5]},
                              {"rows_held_in_band"}),
        "a_row_dropped": ({"rows_dropped": 3.0}, {"no_row_dropped"}),
        "state_unchanged": ({"move_rel_err": 1.0, "move_norm": 0.0},
                            {"first_units_move"}),
        "loss_rose": ({"loss_fell": False}, {"loss_fell"}),
    }.get(name) or (SOUND["controls"][name]["read"],
                    set(SOUND["controls"][name]["failed_checks"]))
    checks = train_lm.decide({**sound, **change}, limits(), True)
    assert {k for k, ok in checks.items() if not ok} == failing
    if name in SOUND["controls"]:  # refused, each by the gradient at least
        assert "gradient_by_group" in failing


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
            "ssm_chunk_log_decay_min"} <= set(line["metrics"])
    assert line["metrics"]["ssm_chunk_log_decay_min"]["value"] < 0
    checks = next(l["value"] for l in lines if l.get("detail") == "checks")
    read = checks["read"]
    assert read["rows_dropped"] == 0 and read["rows_expected"] == 48
    assert set(read["grad_rel_err_by_group"]) == set(
        CONFIG["comparison"]["leaf_groups"])
    assert 0 < read["grad_rel_err_by_group"]["router"]  # the balance term's
    assert 0 < read["grad_rel_err_by_group"]["ssm"]
    # the published limits stand in the configuration; the rehearsal widens
    assert checks["limits"]["grad_rtol"] == 0.3
