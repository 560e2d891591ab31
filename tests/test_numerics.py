"""The numerics analysis stack: the precision-dataflow model behind
MPT020-022 (analysis/numerics.py), the rules themselves, the `numerics`
CLI, and the RT104 runtime numerics sanitizer.

The fixture fires-exactly-once contract lives with every other rule in
test_analysis.py; here each seeded fixture additionally goes QUIET when
its one bug is fixed (the other half of the resolve-or-skip bar), and
the model's load-bearing behaviors — EF pairing in-function and through
one caller level, ef-off markers, push-path gating, mode/scale
provenance, the lockfile precision column — are pinned directly.
"""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from mpit_tpu import quant
from mpit_tpu.analysis import lint
from mpit_tpu.analysis import runtime as rt
from mpit_tpu.analysis import schema as schema_mod

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "analysis"

NUMERICS_ONLY = ("MPT020", "MPT021", "MPT022")


def _lint_source(tmp_path, source, only=NUMERICS_ONLY):
    f = tmp_path / "mod.py"
    f.write_text(source)
    return lint.run_lint(
        [f], lint.Config(hot_all=True, only_rules=only)
    )


def _fixed_fixture(tmp_path, name, old, new):
    src = (FIXTURES / name).read_text()
    assert old in src, f"fixture {name} drifted: {old!r} not found"
    out = tmp_path / name
    out.write_text(src.replace(old, new))
    return lint.run_lint([out], lint.Config(hot_all=True))


# ------------------------------------------------------- quiet when fixed


def test_mpt020_fixture_quiet_when_reducing_the_reconstruction(tmp_path):
    findings = _fixed_fixture(
        tmp_path,
        "fixture_mpt020.py",
        "jnp.sum(codes, axis=0)",
        "jnp.sum(deq, axis=0)",
    )
    assert findings == [], [f.format() for f in findings]


def test_mpt021_fixture_quiet_when_residual_is_folded(tmp_path):
    findings = _fixed_fixture(
        tmp_path,
        "fixture_mpt021.py",
        "    q = quantize(delta, \"int8\")\n"
        "    transport.send(rank, TAG_GRAD_PUSH, q)",
        "    q = quantize(delta, \"int8\")\n"
        "    residual = delta - dequantize(q)\n"
        "    transport.send(rank, TAG_GRAD_PUSH, q)\n"
        "    return residual",
    )
    assert findings == [], [f.format() for f in findings]


def test_mpt021_fixture_quiet_under_an_ef_off_marker(tmp_path):
    findings = _fixed_fixture(
        tmp_path,
        "fixture_mpt021.py",
        "    q = quantize(delta, \"int8\")",
        "    # mpit-analysis: ef-off[test: stateless by design]\n"
        "    q = quantize(delta, \"int8\")",
    )
    assert findings == [], [f.format() for f in findings]


def test_mpt022_fixture_quiet_when_mode_and_scale_match(tmp_path):
    findings = _fixed_fixture(
        tmp_path,
        "fixture_mpt022.py",
        'dequantize_rows_jnp(codes, None, "bf16")',
        'dequantize_rows_jnp(codes, scales, "int8")',
    )
    assert findings == [], [f.format() for f in findings]


# ------------------------------------------------------- model behaviors


def test_pairing_resolves_through_one_caller_level(tmp_path):
    # the _quant_allreduce_leaf shape: the leaf RETURNS the
    # reconstruction and the caller folds the residual — paired, not
    # unpaired, even though the Sub is a function away
    findings = _lint_source(
        tmp_path,
        "from mpit_tpu.quant import dequantize_jnp, quantize_jnp\n"
        "def leaf(x, mode):\n"
        "    codes, scale = quantize_jnp(x, mode)\n"
        "    sent = dequantize_jnp(codes, scale, mode)\n"
        "    return codes, sent\n"
        "def caller(transport, x, mode):\n"
        "    codes, sent = leaf(x, mode)\n"
        "    residual = x - sent\n"
        "    transport.send(0, 7, (codes,))\n"
        "    return residual\n",
    )
    assert findings == [], [f.format() for f in findings]


def test_unresolved_escape_makes_no_claim(tmp_path):
    # codes returned to callers outside the module: the pass must skip,
    # never guess (the transport/fuzz.py generator shape)
    findings = _lint_source(
        tmp_path,
        "from mpit_tpu.quant import quantize\n"
        "def gen(rng):\n"
        "    return quantize(rng.standard_normal(8), \"int8\")\n",
    )
    assert findings == [], [f.format() for f in findings]


def test_local_quantize_without_a_send_makes_no_claim(tmp_path):
    findings = _lint_source(
        tmp_path,
        "from mpit_tpu.quant import quantize\n"
        "def roundtrip_only(x):\n"
        "    q = quantize(x, \"int8\")\n"
        "    return None\n",
    )
    assert findings == [], [f.format() for f in findings]


def test_collective_hop_counts_as_the_wire(tmp_path):
    # codes reaching lax.all_to_all are on the exchange path even with
    # no literal send() — unpaired must still fire
    findings = _lint_source(
        tmp_path,
        "from jax import lax\n"
        "from mpit_tpu.quant import quantize_rows_jnp\n"
        "def exchange(rows, axis):\n"
        "    codes, scales = quantize_rows_jnp(rows, \"int8\")\n"
        "    return lax.all_to_all(codes, axis, 0, 0)\n",
    )
    assert [f.rule for f in findings] == ["MPT021"], [
        f.format() for f in findings
    ]


def test_mode_resolves_through_a_local_constant(tmp_path):
    findings = _lint_source(
        tmp_path,
        "from mpit_tpu.quant import dequantize_rows_jnp, quantize_rows_jnp\n"
        "def roundtrip(rows):\n"
        "    push_mode = \"int8\"\n"
        "    codes, scales = quantize_rows_jnp(rows, push_mode)\n"
        "    deq = dequantize_rows_jnp(codes, scales, \"bf16\")\n"
        "    return rows - deq\n",
    )
    assert [f.rule for f in findings] == ["MPT022"], [
        f.format() for f in findings
    ]
    assert "'int8'" in findings[0].message


def test_scale_reused_across_chunks_is_flagged(tmp_path):
    findings = _lint_source(
        tmp_path,
        "from mpit_tpu.quant import dequantize_jnp, quantize_jnp\n"
        "def mixup(a, b):\n"
        "    ca, sa = quantize_jnp(a, \"int8\")\n"
        "    cb, sb = quantize_jnp(b, \"int8\")\n"
        "    bad = dequantize_jnp(cb, sa, \"int8\")\n"
        "    r1 = a - dequantize_jnp(ca, sa, \"int8\")\n"
        "    r2 = b - dequantize_jnp(cb, sb, \"int8\")\n"
        "    return bad, r1, r2\n",
    )
    assert [f.rule for f in findings] == ["MPT022"], [
        f.format() for f in findings
    ]
    assert "scale" in findings[0].message


def test_unresolved_mode_reduce_still_fires_on_codes(tmp_path):
    # operand provenance (codes) is enough for MPT020 even when the
    # mode variable never resolves to a literal
    findings = _lint_source(
        tmp_path,
        "import jax.numpy as jnp\n"
        "from mpit_tpu.quant import quantize_rows_jnp\n"
        "def reduce_codes(rows, mode):\n"
        "    codes, scales = quantize_rows_jnp(rows, mode)\n"
        "    return jnp.sum(codes, axis=0)\n",
        only=("MPT020",),
    )
    assert [f.rule for f in findings] == ["MPT020"]


def test_f32_astype_upcast_silences_mpt020(tmp_path):
    # an explicit astype(float32) is the sanctioned escape hatch: the
    # value is no longer claimed to be codes
    findings = _lint_source(
        tmp_path,
        "import jax.numpy as jnp\n"
        "from mpit_tpu.quant import quantize_rows_jnp\n"
        "def reduce_upcast(rows, mode):\n"
        "    codes, scales = quantize_rows_jnp(rows, mode)\n"
        "    return jnp.sum(codes.astype(jnp.float32) * scales, axis=0)\n",
        only=("MPT020",),
    )
    assert findings == [], [f.format() for f in findings]


def test_tag_precision_column_derivation():
    assert schema_mod.tag_precision(["(int, quant)"], []) == ["codes"]
    assert schema_mod.tag_precision(["ndarray"], ["quant"]) == [
        "codes",
        "f32",
    ]
    assert schema_mod.tag_precision(["(int, int)"], ["tuple"]) == []


def test_lockfile_precision_drift_is_flagged(tmp_path):
    # a repo whose lock pins ["codes"] for a tag whose senders now carry
    # plain ints: the drift leg anchors MPT022 at the sender site
    pkg = tmp_path / "repo"
    # keep the package NAME: tag constants resolve through the
    # `fixture_mpt016.tags` import, so the directory must match
    shutil.copytree(FIXTURES / "fixture_mpt016", pkg / "fixture_mpt016")
    (pkg / "pyproject.toml").write_text("[project]\nname = 'probe'\n")
    lock = {
        "version": schema_mod.SCHEMA_LOCK_VERSION,
        "tags": {
            "26": {
                "name": "TAG_DATA",
                "sender": [],
                "receiver": [],
                "precision": ["codes"],
            }
        },
        "snapshot": {"writes": [], "reads": []},
    }
    (pkg / schema_mod.SCHEMA_LOCK_FILENAME).write_text(json.dumps(lock))
    findings = lint.run_lint(
        [pkg / "fixture_mpt016"],
        lint.Config(hot_all=True, only_rules=("MPT022",)),
    )
    assert [f.rule for f in findings] == ["MPT022"], [
        f.format() for f in findings
    ]
    assert "precision drifted" in findings[0].message


def test_package_scan_has_no_unpaired_ef_and_documents_ef_off():
    """The whole-package ledger the PR signed off on: every quantize
    site is paired, annotated ef-off, or makes no claim — and the three
    deliberately-stateless paths carry their markers."""
    from mpit_tpu.analysis import numerics

    modules = []
    for ap, rel in lint.collect_files([REPO / "mpit_tpu"]):
        ctx = lint.load_module(ap, rel)
        if ctx is not None:
            modules.append(ctx)
    project = lint.Project(modules=modules, config=lint.Config())
    doc = numerics.build_model(project).to_json()
    by_ef = {}
    for q in doc["quant_sites"]:
        by_ef.setdefault(q["ef"], []).append(q["site"])
    assert "unpaired" not in by_ef, by_ef
    assert len(by_ef.get("ef-off", [])) == 4, by_ef  # the 3 documented
    # paths (pserver's spans two sites: list and legacy chunk)


# ------------------------------------------------------------------- CLI


def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "mpit_tpu.analysis", *args],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )


def test_cli_numerics_json_dump():
    proc = _cli("numerics", "--json", "--package",
                str(FIXTURES / "fixture_mpt022.py"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert len(doc["quant_sites"]) == 1
    assert doc["quant_sites"][0]["ef"] == "paired"
    assert len(doc["dequant_sites"]) == 1
    assert doc["dequant_sites"][0]["declared_mode"] == "bf16"
    assert doc["dequant_sites"][0]["codes_mode"] == "int8"


def test_cli_only_numerics_rule_gates_like_the_others():
    proc = _cli("--no-baseline", "--only", "MPT021",
                str(FIXTURES / "fixture_mpt021.py"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "MPT021" in proc.stdout
    proc = _cli("--no-baseline", "--only", "MPT020",
                str(FIXTURES / "fixture_mpt021.py"))
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ------------------------------------------------------------------ RT104


def test_rt104_silent_on_a_clean_quantized_round():
    with rt.checking(numerics=True) as ck:
        clean = np.arange(12, dtype=np.float32).reshape(3, 4)
        clean[1] = 0.0  # legitimate zero-absmax row
        codes, scales = quant.quantize_rows(clean, "int8")
        quant.dequantize_rows(codes, scales, "int8")
        quant.dequantize(quant.quantize(clean.ravel(), "int8"))
        quant.quantize(np.zeros(0, np.float32), "int8")  # empty chunk
    assert ck.findings == [], ck.findings


def test_rt104_catches_seeded_nan_once_per_site_with_stack():
    poisoned = np.ones(8, np.float32)
    poisoned[3] = np.nan
    with rt.checking(numerics=True) as ck:
        for _ in range(3):  # dedup: one report per call site
            quant.quantize(poisoned, "int8")
    rules = [f.rule for f in ck.findings]
    assert rules == ["RT104"], ck.findings
    assert "non-finite" in ck.findings[0].message
    assert 'File "' in ck.findings[0].message  # carries the stack


def test_rt104_catches_bad_dequant_scale():
    codes = np.array([1, 2, 3], np.int8)
    with rt.checking(numerics=True) as ck:
        quant.dequantize(quant.QuantArray("int8", float("inf"), codes))
    assert [f.rule for f in ck.findings] == ["RT104"], ck.findings


def test_rt104_zero_absmax_row_with_nonzero_codes():
    # can't be produced by the hardened kernels — drive the checker
    # directly, the way a future buggy kernel would
    with rt.checking(numerics=True) as ck:
        arr = np.zeros((2, 4), np.float32)
        codes = np.array([[0, 0, 0, 0], [7, 0, 0, 0]], np.int8)
        scales = np.ones((2, 1), np.float32)
        ck.on_quantize("quantize_rows", arr, "int8", scales, codes)
    assert [f.rule for f in ck.findings] == ["RT104"], ck.findings
    assert "zero-absmax" in ck.findings[0].message


def test_rt104_residual_norm_boundedness():
    with rt.checking(numerics=True) as ck:
        for _ in range(rt.RuntimeChecker._RESID_WARMUP):
            rt.note_residual_norm("t.ef", 0.5)
        rt.note_residual_norm("t.ef", 0.6)  # bounded: fine
        assert ck.findings == []
        rt.note_residual_norm(
            "t.ef", 0.5 * rt.RuntimeChecker.RESIDUAL_GROWTH_BOUND * 2
        )
    assert [f.rule for f in ck.findings] == ["RT104"], ck.findings
    assert "diverging" in ck.findings[0].message


def test_rt104_nonfinite_residual_norm():
    with rt.checking(numerics=True) as ck:
        rt.note_residual_norm("t.ef2", float("nan"))
    assert [f.rule for f in ck.findings] == ["RT104"], ck.findings


def test_rt104_server_apply_boundary():
    bad = np.ones(16, np.float32)
    bad[5] = np.inf
    with rt.checking(numerics=True) as ck:
        rt.note_numeric_array("pserver.apply", np.ones(16, np.float32))
        assert ck.findings == []
        rt.note_numeric_array("pserver.apply", bad)
    assert [f.rule for f in ck.findings] == ["RT104"], ck.findings


def test_rt104_off_means_zero_hooks():
    # race-only checker: the numerics hooks must stay dormant
    poisoned = np.ones(4, np.float32)
    poisoned[0] = np.nan
    with rt.checking(race=True) as ck:
        quant.quantize(poisoned, "int8")
        rt.note_residual_norm("t.off", float("nan"))
        rt.note_numeric_array("t.off", poisoned)
    assert [f for f in ck.findings if f.rule == "RT104"] == []


# --------------------------------------------- quantization error bound
#
# The property the whole EF story leans on (docs/WIRE.md): for every
# finite element, |dequantize(quantize(x)) - x| <= scale/2 for int8 (a
# finite input never reconstructs to inf, FLT_MAX included) and relative
# error <= 2^-8 for bf16 up to half a step past bfloat16's largest finite
# value (from there round-to-nearest-even gives infinity, as IEEE 754
# says) — INCLUDING arrays poisoned with NaN/Inf/-0.0, empty chunks, and
# all-zero blocks, where the hardened kernels must stay finite rather
# than accurate. Runs under hypothesis when available; otherwise a
# seeded-stdlib sweep covers the same space so the property still
# executes in tier-1.

_EDGE_VALUES = np.array(
    [0.0, -0.0, np.nan, np.inf, -np.inf, 1.0, -1.0,
     2.0 ** -120, 6.5e4, 3.0e38, -3.0e38,
     # what hypothesis found: 127 * fl(FLT_MAX / 127) overflowed; the
     # second lies half a step above bfloat16's largest finite value,
     # the third just under that; a subnormal's scale underflowed to zero
     3.4028234663852886e38, 3.39617752923046e38, 3.3895315920756315e38,
     quant.BF16_MAX, -quant.BF16_MAX, 1.401298464324817e-45],
    np.float32,
)
# and two NaNs by their bits: bf16's rounding carried the first's payload
# through the sign bit to -0.0 and left the second, whose payload is all
# in the low half, as an infinity
_EDGE_VALUES = np.concatenate([
    _EDGE_VALUES,
    np.array([0x7FFFF8EC, 0x7F800001], np.uint32).view(np.float32),
])


def _assert_roundtrip_bound(a):
    a = np.asarray(a, np.float32)
    finite = np.isfinite(a)
    # int8: finite scale/codes always; half-step absolute bound on the
    # finite lanes; NaN lanes reconstruct to exactly 0
    q = quant.quantize(a, "int8")
    assert np.isfinite(q.scale) and q.scale > 0
    assert np.abs(q.data).max(initial=0) <= 127
    out = quant.dequantize(q)
    assert np.isfinite(out).all()
    if finite.any():
        err = np.abs(out[finite] - a[finite])
        assert err.max() <= q.scale * 0.51, (a, q.scale, err.max())
    assert (out[np.isnan(a)] == 0).all()
    # bf16: lanes pass through the f32<->bf16 pair with <= 2^-8 relative
    # error on normal finite values up to half a step past bfloat16's
    # largest; from there the nearest bfloat16 is infinity (the tie goes
    # to the even code, infinity's); NaN stays NaN (representable)
    out = quant.dequantize(quant.quantize(a, "bf16"))
    over = finite & (np.abs(a) >= quant.BF16_MAX + np.float32(2.0 ** 119))
    assert (out[over] == np.copysign(np.inf, a[over])).all()
    normal = finite & ~over & (np.abs(a) >= 2.0 ** -100)
    nz = normal & (a != 0)
    if nz.any():
        rel = np.abs(out[nz] - a[nz]) / np.abs(a[nz])
        assert rel.max() <= 2.0 ** -8, (a, rel.max())
    assert np.isnan(out[np.isnan(a)]).all()
    # rows face: bit-equal to quantizing each row independently
    if a.size and a.size % 4 == 0:
        rows = a.reshape(-1, 4)
        codes, scales = quant.quantize_rows(rows, "int8")
        for j in range(rows.shape[0]):
            per_row = quant.quantize(rows[j], "int8")
            np.testing.assert_array_equal(codes[j], per_row.data)
            assert float(scales[j, 0]) == per_row.scale


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    def test_quantize_roundtrip_error_bound_property():
        rng = np.random.default_rng(0x20C)
        _assert_roundtrip_bound(np.zeros(0, np.float32))  # empty chunk
        _assert_roundtrip_bound(_EDGE_VALUES)
        for _ in range(200):
            n = int(rng.integers(0, 64))
            a = (
                rng.standard_normal(n)
                * np.float32(10.0) ** rng.integers(-6, 7)
            ).astype(np.float32)
            for _ in range(int(rng.integers(0, 4))):
                if n:
                    a[rng.integers(0, n)] = _EDGE_VALUES[
                        rng.integers(len(_EDGE_VALUES))
                    ]
            _assert_roundtrip_bound(a)
else:
    @settings(max_examples=300, deadline=None)
    @given(
        st.lists(
            st.floats(
                width=32, allow_nan=True, allow_infinity=True,
                allow_subnormal=True,
            ),
            max_size=64,
        )
    )
    def test_quantize_roundtrip_error_bound_property(xs):
        _assert_roundtrip_bound(np.array(xs, np.float32))
