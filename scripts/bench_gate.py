#!/usr/bin/env python
"""Compare BENCH_*.json snapshots; flag regressions.

    python scripts/bench_gate.py [--strict] [--trend] [--threshold 0.10] [DIR]

The driver writes one ``BENCH_r<NN>.json`` per round (``n``, ``cmd``,
``rc``, ``tail``, ``parsed`` = the bench's JSON line). This gate reads
the two newest, matches them by metric, and flags movement beyond the
threshold in the direction that hurts:

- throughput (``value``) dropping;
- latency fields (``*_ms``) rising;
- ``goodput`` dropping;
- update-quality fields under ``dynamics`` (mnist-ps legs) moving in
  the direction that hurts: ``staleness_p99`` or ``elastic_dist_final``
  rising, ``norm_ratio`` drifting either way (its healthy value is an
  equilibrium, not a maximum). A field newly appearing from a zero/
  absent baseline warns too — quality cost showing up where there was
  none is exactly what an async-speedup "win" must disclose.

``--trend`` additionally scores the newest round against the BEST round
in the longest comparable history suffix (same metric, same platform
mode): five rounds each 3% slower never trip the pairwise 10% gate, but
the newest-vs-peak comparison catches the accumulated drift. The trend
pass uses the same ``--threshold`` and prints the series it scored.

Rounds measured on different platforms (a TPU round vs a CPU wiring
run, visible via ``platform``) are
reported but never flagged — a 1000x "regression" between a TPU number
and a CPU number is a platform change, not a code change. The same
rule applies to the exchange configuration: rounds with different
quant/bucket/overlap modes (``dp_quant``/``dp_bucket_bytes``/
``dp_overlap`` on the collective legs, ``wire_format``/``wire_quant``
on the PS legs) are never scored against each other — an int8 round
"regressing" against a raw round is an A/B comparison, not a drift,
and it belongs in the bench's own ``vs_raw`` field. Membership-churn
runs (``elastic_churn`` truthy: ranks killed and respawned mid-run by
the elastic supervisor) are likewise their own comparability mode —
a soak that loses a rank every few seconds measures recovery cost,
not steady-state throughput, and must never be trended against a
stable-membership round.

Warn-only by default (exit 0 with warnings printed) because bench noise
must not block commits — scripts/lint.sh runs it that way (with
``--trend``). ``--strict`` exits 1 on flags (pairwise or trend) for CI
lanes that do gate on trajectory. Exit 2 on usage errors only; fewer
than two comparable snapshots is a clean pass (nothing to compare is
not a regression).

Stdlib-only and import-free of the package: safe in pre-commit hooks.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys


def _load_rounds(bench_dir: str) -> list:
    """BENCH_*.json files with a parsed metric, oldest -> newest (by the
    round counter ``n``, falling back to filename order)."""
    rounds = []
    for path in sorted(glob.glob(os.path.join(bench_dir, "BENCH_*.json"))):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        parsed = data.get("parsed")
        if not isinstance(parsed, dict) or "metric" not in parsed:
            continue
        rounds.append((data.get("n", 0), path, parsed))
    rounds.sort(key=lambda r: r[0])
    return rounds


def _platform_mode(parsed: dict) -> str:
    """Comparable-measurement key: CPU wiring runs must not be scored
    against real-hardware rounds."""
    return str(parsed.get("platform", "unknown"))


_EXCHANGE_KEYS = (
    # collective-exchange knobs (bench.py --dp / quantized trainers)
    "dp_quant", "dp_bucket_bytes", "dp_overlap",
    # PS socket-codec knobs (bench.py --preset mnist-ps)
    "wire_format", "wire_quant",
    # elastic-membership churn (scripts/elastic_soak.sh legs): a run
    # that kills/respawns ranks measures recovery, not steady state
    "elastic_churn",
    # sharded-PS topology: shard count and ring membership version both
    # change who serves which slice — a resharded round is a different
    # exchange, not a slower one
    "ps_shards", "ring_version",
    # serving-fleet shape (bench.py --load --fleet N): per-replica
    # goodput/latency scales with fleet size, and the routing policy
    # changes which replica absorbs the tail — different fleet, not a
    # regression
    "replica_count", "router_policy",
)


def _exchange_mode(parsed: dict) -> str:
    """Comparable-measurement key #2: rounds with different quant/
    bucket/overlap (or wire codec) modes are A/B variants of each
    other, not points on one trajectory — never score them pairwise."""
    return "/".join(str(parsed.get(k, "-")) for k in _EXCHANGE_KEYS)


_MS_KEY = re.compile(r"_ms$")


def compare(old: dict, new: dict, threshold: float) -> list:
    """Regression strings for one metric's old -> new movement."""
    flags = []

    def _num(d, k):
        v = d.get(k)
        return v if isinstance(v, (int, float)) and not isinstance(
            v, bool
        ) else None

    ov, nv = _num(old, "value"), _num(new, "value")
    if ov is not None and nv is not None and ov > 0:
        drop = (ov - nv) / ov
        if drop > threshold:
            flags.append(
                f"value {ov} -> {nv} ({drop:.1%} drop, "
                f"unit {new.get('unit', '?')})"
            )
    for k in sorted(set(old) & set(new)):
        if not _MS_KEY.search(k):
            continue
        ov, nv = _num(old, k), _num(new, k)
        if ov is None or nv is None or ov <= 0:
            continue
        rise = (nv - ov) / ov
        if rise > threshold:
            flags.append(f"{k} {ov} -> {nv} ({rise:.1%} rise)")
    ov, nv = _num(old, "goodput"), _num(new, "goodput")
    if ov is not None and nv is not None and ov > 0:
        drop = (ov - nv) / ov
        if drop > threshold:
            flags.append(f"goodput {ov} -> {nv} ({drop:.1%} drop)")
    od = old.get("dynamics") if isinstance(old.get("dynamics"), dict) else {}
    nd = new.get("dynamics") if isinstance(new.get("dynamics"), dict) else {}
    for k in ("staleness_p99", "elastic_dist_final"):
        ov, nv = _num(od, k), _num(nd, k)
        if nv is None:
            continue
        if ov is not None and ov > 0:
            rise = (nv - ov) / ov
            if rise > threshold:
                flags.append(f"dynamics.{k} {ov} -> {nv} "
                             f"({rise:.1%} rise)")
        elif nv > 0 and od:  # baseline had dynamics but this value was 0
            flags.append(f"dynamics.{k} 0 -> {nv} (quality cost "
                         "appeared from a zero baseline)")
    ov, nv = _num(od, "norm_ratio"), _num(nd, "norm_ratio")
    if ov is not None and nv is not None and ov > 0:
        drift = abs(nv - ov) / ov
        if drift > threshold:
            flags.append(f"dynamics.norm_ratio {ov} -> {nv} "
                         f"({drift:.1%} drift)")
    return flags


def comparable_series(rounds: list) -> list:
    """The longest suffix of ``rounds`` sharing the newest round's
    metric and platform mode — the history the trend pass scores."""
    if not rounds:
        return []
    newest = rounds[-1][2]
    key = (
        newest.get("metric"),
        _platform_mode(newest),
        _exchange_mode(newest),
    )
    series: list = []
    for item in reversed(rounds):
        parsed = item[2]
        if (
            parsed.get("metric"),
            _platform_mode(parsed),
            _exchange_mode(parsed),
        ) != key:
            break
        series.append(item)
    series.reverse()
    return series


def trend(rounds: list, threshold: float) -> tuple[list, str]:
    """(flag strings, series label) for newest-vs-best-of-history drift.

    Best means per-key best: max for ``value``/``goodput``, min for each
    ``*_ms`` — a single strong round anywhere in the comparable history
    is the standard the newest must stay within ``threshold`` of."""
    series = comparable_series(rounds)
    if len(series) < 3:
        # pairwise already covers 2; a 2-round "trend" would double-warn
        return [], ""
    newest_n, newest_path, newest = series[-1]
    history = [p for _, _, p in series[:-1]]
    label = (
        f"{os.path.basename(series[0][1])}.."
        f"{os.path.basename(newest_path)} "
        f"({len(series)} rounds, {newest.get('metric')}, "
        f"{_platform_mode(newest)})"
    )

    def _num(d, k):
        v = d.get(k)
        return v if isinstance(v, (int, float)) and not isinstance(
            v, bool
        ) else None

    flags = []
    for key, best_of in (("value", max), ("goodput", max)):
        vals = [
            (v, i) for i, p in enumerate(history)
            if (v := _num(p, key)) is not None and v > 0
        ]
        nv = _num(newest, key)
        if not vals or nv is None:
            continue
        best, at = best_of(vals)
        drop = (best - nv) / best
        if drop > threshold:
            flags.append(
                f"{key} peaked at {best} in "
                f"{os.path.basename(series[at][1])}, now {nv} "
                f"({drop:.1%} below peak)"
            )
    ms_keys = sorted(
        k for k in newest if _MS_KEY.search(k)
        if isinstance(newest.get(k), (int, float))
    )
    for k in ms_keys:
        vals = [
            (v, i) for i, p in enumerate(history)
            if (v := _num(p, k)) is not None and v > 0
        ]
        nv = _num(newest, k)
        if not vals or nv is None or nv <= 0:
            continue
        best, at = min(vals)
        rise = (nv - best) / best
        if rise > threshold:
            flags.append(
                f"{k} best was {best} in "
                f"{os.path.basename(series[at][1])}, now {nv} "
                f"({rise:.1%} above best)"
            )
    return flags, label


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    strict = "--strict" in argv
    if strict:
        argv.remove("--strict")
    trend_mode = "--trend" in argv
    if trend_mode:
        argv.remove("--trend")
    threshold = 0.10
    if "--threshold" in argv:
        i = argv.index("--threshold")
        try:
            threshold = float(argv[i + 1])
            del argv[i:i + 2]
        except (IndexError, ValueError):
            print("--threshold needs a number", file=sys.stderr)
            return 2
    if len(argv) > 1:
        print(__doc__.strip().splitlines()[2].strip(), file=sys.stderr)
        return 2
    bench_dir = argv[0] if argv else "."

    rounds = _load_rounds(bench_dir)
    if len(rounds) < 2:
        print(f"bench_gate: {len(rounds)} snapshot(s) under "
              f"{bench_dir} — nothing to compare")
        return 0
    (_, old_path, old), (_, new_path, new) = rounds[-2], rounds[-1]

    any_flags = False
    if old.get("metric") != new.get("metric"):
        print(f"bench_gate: metric changed "
              f"{old.get('metric')} -> {new.get('metric')} — skipping")
    elif (om := _platform_mode(old)) != (nm := _platform_mode(new)):
        print(f"bench_gate: platform changed {om} -> {nm} "
              f"({os.path.basename(old_path)} -> "
              f"{os.path.basename(new_path)}) — not comparable")
    elif (oe := _exchange_mode(old)) != (ne := _exchange_mode(new)):
        print(f"bench_gate: exchange mode changed {oe} -> {ne} "
              f"({os.path.basename(old_path)} -> "
              f"{os.path.basename(new_path)}) — not comparable "
              "(quant/bucket/overlap A/B, not a trajectory)")
    else:
        flags = compare(old, new, threshold)
        label = (f"{os.path.basename(old_path)} -> "
                 f"{os.path.basename(new_path)} "
                 f"({new.get('metric')}, {nm})")
        if not flags:
            print(f"bench_gate: OK {label}")
        for f in flags:
            print(f"bench_gate: WARNING {label}: {f}")
            any_flags = True

    if trend_mode:
        tflags, tlabel = trend(rounds, threshold)
        if tlabel and not tflags:
            print(f"bench_gate: trend OK {tlabel}")
        for f in tflags:
            print(f"bench_gate: TREND WARNING {tlabel}: {f}")
            any_flags = True

    return 1 if (strict and any_flags) else 0


if __name__ == "__main__":
    sys.exit(main())
