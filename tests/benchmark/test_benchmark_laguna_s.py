"""What PR 27 added to the benchmark: the configuration's file against the
catalog's row, the benchmark's copy of the reference against the program's,
the kernels' operation counts against counts by hand, the new reduction on
planes built by hand, and the new cell's traced rehearsal. CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import lm_kernels, lm_spans  # noqa: E402

CELL = "laguna_s21_sync_1chip_8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["attn_window_ms_unit", "attn_full_ms_unit", "moe_route_ms_unit",
       "moe_experts_ms_unit", "moe_load_max_over_mean",
       "flash_window_roofline_pct", "flash_causal_roofline_pct"]
REDUCED = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 12544}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = load("benchmark", "configs", "laguna-s-2.1.json")


# -- the configuration ---------------------------------------------------------

def test_every_published_key_is_held_and_only_the_three_cuts_differ():
    """Against the file's own copy of the source's config: every key at the
    top level under the same name, equal but for the keys in ``reduced``."""
    published = CONFIG["source_config"]
    assert CONFIG["reduced"] == list(REDUCED)
    for key, value in published.items():
        if key in REDUCED:
            assert CONFIG[key] == REDUCED[key] != value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["deployment"]["published"] == {
        k: published[k] for k in REDUCED}
    assert CONFIG["deployment"]["chips_sharing_each_layer"] * CONFIG[
        "num_experts"] == published["num_experts"]
    assert CONFIG["share"]["num_routed_experts"] == published["num_experts"]
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"]
    manifest = next(c for c in load("BENCHMARK.json")["configs"]
                    if c["name"] == "laguna-s-2.1")
    assert manifest["source"] == CONFIG["source"]
    assert manifest["reduced"] == CONFIG["reduced"]


def test_the_optimizer_is_the_issues_and_the_routing_assumption_is_stated():
    """AdamW 3e-4, 100 warm-up steps then cosine, weight decay 1e-4 (the
    repo's preset, as ``gpt2-small`` assumes); what keeps the experts held
    in use is an assumption about the router, written down, not a slower
    optimizer: the family's balance loss, and no gradient through the
    routing weights of a share."""
    train = CONFIG["train_config"]
    preset = load("benchmark", "configs", "gpt2-small.json")["train_config"]
    for key in ("optimizer", "lr", "lr_schedule", "warmup_steps",
                "weight_decay"):
        assert train[key] == preset[key], key
    assert (train["optimizer"], train["lr"], train["warmup_steps"],
            train["weight_decay"]) == ("adamw", 3e-4, 100, 1e-4)
    job = load("benchmark", "workloads", f"{CELL}.json")
    for key, value in (("moe_routing_no_grad", True),
                       ("router_aux_loss_coef", 0.001)):
        assert CONFIG["share"][key] == value
        assert any(a.startswith(key) for a in CONFIG["assumed"]), key
        assert job["rehearsal"]["train_config"]["arch"][key] == value


def test_the_source_config_is_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-S-2.1")
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["source_config"] == row["config"]


def test_no_width_is_cut_and_the_floors_are_kept():
    published = CONFIG["source_config"]
    for key in ("hidden_size", "intermediate_size", "head_dim",
                "num_key_value_heads", "num_attention_heads",
                "moe_intermediate_size", "shared_expert_intermediate_size",
                "num_experts_per_tok", "sliding_window", "rope_parameters",
                "num_attention_heads_per_layer", "layer_types",
                "mlp_layer_types"):
        assert CONFIG[key] == published[key], key
    layers = CONFIG["num_hidden_layers"]
    assert CONFIG["mlp_layer_types"][:layers] == ["dense"] + ["sparse"] * 4
    # one whole period of the attention pattern after the leading layer
    assert sorted(CONFIG["layer_types"][1:layers]) == [
        "full_attention"] + ["sliding_attention"] * 3
    assert CONFIG["num_experts"] >= 8 and layers - 1 >= 4


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
    norms = 2 * CONFIG["hidden_size"]
    table = CONFIG["parameters"]
    assert size(tree["Embed_0"]) + size(tree["head"]) == table[
        "embedding_and_head"]
    assert size(tree["Block_0"]) == table["layer_0_full_48_heads_dense_ffn"]
    for l in (1, 2, 3):
        assert size(tree[f"Block_{l}"]) == table[
            "layers_1_to_3_sliding_72_heads_8_experts_each"]
    assert size(tree["Block_4"]) == table["layer_4_full_48_heads_8_experts"]
    assert size(tree) == table["held"] == 811017216
    assert tree["Block_1"]["moe_router"].shape == (3072, 256)
    assert tree["Block_1"]["moe_w_gate"].shape == (8, 3072, 1024)
    assert tree["Block_1"]["wq"].shape == (3072, 72 * 128) and norms == 6144


# -- the two reference files ---------------------------------------------------

def test_the_benchmarks_reference_is_the_programs():
    """The same text below the copy's own first paragraph, and the same
    numbers on a seed."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference_laguna_s as copy
    from mpit_tpu.models import reference_lm as original
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
    share = dict(experts_held=arch["num_experts"], expert_offset=0)
    both = [jax.jit(lambda p, m=m: m.loss_and_grad(
        p, tokens, tokens, arch, **share))(params) for m in (original, copy)]
    for a, b in zip(*(jax.tree.leaves(x) for x in both)):
        np.testing.assert_array_equal(a, b)


# -- the kernels' operations and bytes ----------------------------------------

def test_live_pairs_are_counted_from_the_mask():
    for t, w in ((64, 8), (64, None), (8192, 512), (33, 40)):
        i, j = np.arange(t)[:, None], np.arange(t)[None, :]
        seen = (j <= i) & (True if w is None else j > i - w)
        assert lm_kernels.live_pairs(t, w) == seen.sum()


def test_kernel_flops_and_bytes_by_hand():
    shape = {"batch": 1, "heads": 72, "kv_heads": 8, "t": 8192, "d": 128,
             "window": 512, "itemsize": 2}
    pairs = 512 * 8192 - 512 * 511 // 2
    assert lm_kernels.flops("fwd", shape) == 2 * 2 * 128 * pairs * 72
    assert lm_kernels.flops("dq", shape) == 2 * 3 * 128 * pairs * 72
    assert lm_kernels.flops("dkv", shape) == 2 * 4 * 128 * pairs * 72
    q, kv, stat = 8192 * 72 * 128 * 2, 8192 * 8 * 128 * 2, 8192 * 72 * 4
    assert lm_kernels.bytes_moved("fwd", shape) == 2 * q + 2 * kv + stat
    assert lm_kernels.bytes_moved("dkv", shape) == 2 * q + 4 * kv + 2 * stat
    # the forward is bound by operations, not by bytes, on the v5e
    least = lm_kernels.least_seconds("fwd", shape, "TPU v5 lite")
    assert least == lm_kernels.flops("fwd", shape) / 197e12
    with pytest.raises(KeyError):
        lm_kernels.least_seconds("fwd", shape, "no such chip")


# -- the reduction, on planes built by hand ------------------------------------

STEP = "jit(train_step)/shard_map/jvp(TransformerLM)/Block_1"
OP_NAMES = {
    "fusion.1": f"{STEP}/attn_proj/rope/mul",
    "flash_window_fwd.3": f"{STEP}/attention/attn_window/flash_window_fwd/pallas_call",
    "flash_window_dkv.4": f"{STEP}/attention/attn_window/flash_window_dkv/pallas_call",
    "fusion.2": f"{STEP}/attention/attn_window/flash_layout/transpose",
    "sort.1": f"{STEP}/mlp/moe_dispatch/sort",
    "fusion.3": f"{STEP}/mlp/moe_router/top_k",
    "ragged-dot.1": f"{STEP}/mlp/moe_experts/ragged_dot",
    "fusion.4": f"{STEP}/mlp/moe_shared/dot_general",
    "fusion.5": f"{STEP}/head/dot_general",
}


def hand_made_plane():
    ops, at = [], 0.0
    for unit in range(2):
        for name, ms in (("fusion.1", 1), ("flash_window_fwd.3", 4),
                         ("fusion.2", 2), ("sort.1", 3), ("fusion.3", 5),
                         ("ragged-dot.1", 6), ("ragged-dot-none.7", 2),
                         ("fusion.4", 7),
                         ("flash_window_dkv.4", 8), ("flash_window_fwd.3", 4),
                         ("fusion.5", 10)):
            ops.append((f"%{name} = f32[8] fusion(...)", at, ms * 1e6))
            at += ms * 1e6
    half = at / 2
    return {"XLA Modules": [("jit_train_step", 0.0, half),
                            ("jit_train_step", half, half)],
            "XLA Ops": ops}


def test_new_scopes_and_kernels_reduce_to_ms_a_unit():
    out = lm_spans.reduce(hand_made_plane(), OP_NAMES)
    assert out["units"] == 2
    assert out["scope_ms_unit"] == pytest.approx({
        "rope": 1, "attn_window": 4 + 2 + 8 + 4, "moe_dispatch": 3,
        # the compiler's grouped kernel carries no op_name: read by name
        "moe_router": 5, "moe_experts": 6 + 2, "moe_shared": 7})
    assert out["kernels"] == {
        "flash_window_dkv": {"calls_unit": 1, "ms_unit": pytest.approx(8)},
        "flash_window_fwd": {"calls_unit": 2, "ms_unit": pytest.approx(8)}}
    assert "attn_full" not in out["scopes_in_program"]


def test_readers_on_a_fixture_run(monkeypatch):
    """Every new reader returns a number from a run that holds the trace,
    the counters and the kernels' shapes, and None from one that does not."""
    import importlib

    reduced = lm_spans.reduce(hand_made_plane(), OP_NAMES)
    monkeypatch.setattr(lm_spans, "traced", lambda: reduced)
    shape = {"batch": 1, "heads": 72, "kv_heads": 8, "t": 8192, "d": 128,
             "window": 512, "itemsize": 2}
    run = {"trace": {"busy_s": 1.0}, "device_kind": "TPU v5 lite",
           "counters": {"moe_load_max_over_mean": [1.5, 1.25, 2.0]},
           "kernels": {"flash_window": shape}}
    read = lambda name, r: importlib.import_module(
        f"benchmark.readers.{name}").read(r)
    assert read("attn_window_ms_unit", run) == pytest.approx(18)
    assert read("attn_full_ms_unit", run) is None  # not in this program
    assert read("moe_route_ms_unit", run) == pytest.approx(8)
    assert read("moe_experts_ms_unit", run) == pytest.approx(15)
    assert read("moe_load_max_over_mean", run) == 1.5
    least_ms = 1e3 * (2 * lm_kernels.least_seconds("fwd", shape, "TPU v5 lite")
                      + lm_kernels.least_seconds("dkv", shape, "TPU v5 lite"))
    assert read("flash_window_roofline_pct", run) == pytest.approx(
        100 * least_ms / 16)
    assert read("flash_causal_roofline_pct", run) is None
    for name in NEW:  # an accepted cell's run: nothing to read, no error
        assert read(name, {"trace": None}) is None


# -- what decides correct, on readings taken on the chip ------------------------

#: a sound run's readings at the published widths (chip run, PR 27)
SOUND = {
    "same_start": True, "first_losses": [9.93170, 9.93170],
    "reference_loss": 9.93165,
    "grad_rel_err_by_group": {
        "attention": 0.041, "dense_ffn": 0.040, "embedding": 0.043,
        "experts": 0.092, "head": 0.030, "norms": 0.041, "router": 0.0,
        "shared": 0.040},
    "move_rel_err": 0.262, "move_norm": 0.0042, "routing_mismatch": 0.0262,
    "rows_dropped": 0.0, "rows_held": [2613.75, 2545.0, 2862.25],
    "rows_expected": 2560.0, "losses_not_finite": 0, "compiled_in_window": 0,
    "loss_fell": True,
}
ALL_OFF = dict.fromkeys(SOUND["grad_rel_err_by_group"], 1.0)


@pytest.mark.parametrize("name,change,failing", [
    ("sound", {}, set()),
    # the reference with float8 operands in the system's place (chip run,
    # PR 27): the loss cannot tell a precision, the gradient and the move do
    ("float8", {"first_losses": [9.92939] * 2,
                "grad_rel_err_by_group": {**ALL_OFF, "head": 0.598},
                "move_rel_err": 1.197},
     {"gradient_by_group", "first_units_move"}),
    # the router left to its partial gradient under 100 warm-up steps (chip
    # run, PR 27): the experts held emptied, then one took a whole layer
    ("routing_collapsed", {"rows_held": [2616.5, 1532.5, 90.5, 2048.0]},
     {"rows_held_in_band"}),
    ("a_row_dropped", {"rows_dropped": 3.0}, {"no_row_dropped"}),
    ("state_unchanged", {"move_rel_err": 1.0, "move_norm": 0.0},
     {"first_units_move"}),
    ("loss_rose", {"loss_fell": False}, {"loss_fell"}),
])
def test_decide_on_chip_readings(name, change, failing):
    from benchmark.drivers import train_lm

    checks = train_lm.decide({**SOUND, **change}, train_lm.LIMITS, True)
    assert {k for k, ok in checks.items() if not ok} == failing
    if name == "loss_rose":  # only where the job asks for it
        assert all(train_lm.decide(
            {**SOUND, **change}, train_lm.LIMITS, False).values())


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
    manifest = load("BENCHMARK.json")
    allowed = {m["name"] for m in manifest["per_layer"]
               if "workloads" not in m or CELL in m["workloads"]}
    assert set(line["metrics"]) <= allowed
    assert {"compile_s", "unit_ms_p50", "input_path_ms_unit",
            "dispatch_host_ms_unit", "init_state_s",
            "moe_load_max_over_mean"} <= set(line["metrics"])
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and m["value"] > 0
    checks = next(l["value"] for l in lines if l.get("detail") == "checks")
    read = checks["read"]
    assert read["rows_dropped"] == 0
    assert set(read["grad_rel_err_by_group"]) == {
        "attention", "dense_ffn", "embedding", "experts", "head", "norms",
        "router", "shared"}
    assert 0 < read["grad_rel_err_by_group"]["router"]  # the balance term's
    # the published limits stand in the driver; the rehearsal widens its own
    from benchmark.drivers import train_lm
    assert checks["limits"]["loss_rtol"] == train_lm.LIMITS["loss_rtol"]
    # the reference's seconds are left out of set-up and of compile_s
    spent = next(l["value"] for l in lines if l.get("detail") == "after_warm_up")
    assert 0 < spent["check_compile_s"] < spent["check_s"]
    assert line["metrics"]["compile_s"]["value"] == pytest.approx(
        spent["seconds"] - spent["check_compile_s"])
