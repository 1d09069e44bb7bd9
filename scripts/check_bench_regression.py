#!/usr/bin/env python
"""Diff two BENCH result files and gate on perf regressions.

``bench.py``'s result lines (and its schema-versioned
``bench_snapshot.json``) only become a CI artifact when a machine can say
"this run is slower than that one" — this script is that gate. It flattens both
files to ``metric -> value``, classifies each metric's improvement
direction by its name suffix, and compares section by section with a
relative tolerance band.

Usage::

    python scripts/check_bench_regression.py BASELINE CANDIDATE \
        [--tol 0.10] [--tol-metric NAME=FRAC ...] [--require-common N]

Accepted input shapes (auto-detected, mixable):

* driver record — ``{"n", "cmd", "rc", "tail", "parsed": {...}}``
* raw BENCH line — ``{"metric", "value", ..., "extra": {...}}``
* bench snapshot — ``{"schema": 1, "primary": {...}, "extra": {...}}``

Direction rules (by metric-name suffix/infix; anything else is
*informational* — reported, never gated)::

    higher is better   _tflops  _tokens_per_s  _speedup*  _vs_xla  _frac  *_goodput*
    lower is better    _ms  _us  _seconds  *_ttft_*  *_p999_*  *_wire_bytes*  *_hbm_bytes*

Zero/missing baselines are skipped (a 0.0 baseline is a section that did
not run, not a number to regress from). Exit codes:
``0`` within tolerance, ``1`` at least one regression, ``2`` usage or
parse error.
"""

from __future__ import annotations

import json
import sys

DEFAULT_TOL = 0.10

HIGHER_SUFFIXES = ("_tflops", "_tokens_per_s", "_vs_xla", "_frac")
# _goodput covers both the counter form (..._goodput_total) and the
# fraction form (..._goodput_frac) of the SLO engine's headline metric.
HIGHER_INFIXES = ("_speedup", "_goodput")
LOWER_SUFFIXES = ("_ms", "_us", "_seconds")
# _p999_ gates tail latencies from the digest sketch (e.g.
# digest_oracle_p999_ms) the same way _ttft_ gates first-token latency.
# _wire_bytes/_hbm_bytes gate traffic volumes: the quantized-operand
# collectives exist to shrink them, so growth IS the regression (e.g.
# serving_quant_ag_wire_bytes creeping back toward its bf16 twin).
LOWER_INFIXES = ("_ttft_", "_p999_", "_wire_bytes", "_hbm_bytes")


def direction(name: str) -> str:
    """'higher' | 'lower' | 'info' for one metric name."""
    if name.endswith(HIGHER_SUFFIXES) or any(s in name for s in HIGHER_INFIXES):
        return "higher"
    if name.endswith(LOWER_SUFFIXES) or any(s in name for s in LOWER_INFIXES):
        return "lower"
    return "info"


def section(name: str) -> str:
    """Group key: the leading name token (``serving_burst_tokens_per_s`` →
    ``serving``) — mirrors bench.py's per-section emission."""
    return name.split("_", 1)[0]


def flatten(doc: dict) -> dict[str, float]:
    """``metric -> value`` from any accepted input shape. Non-numeric and
    nested values (telemetry summaries, tune entries) are ignored."""
    if "parsed" in doc and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]  # driver record -> its parsed BENCH line
    if doc.get("schema") is not None:
        primary, extra = doc.get("primary", {}), doc.get("extra", {})
    else:
        primary, extra = doc, doc.get("extra", {})
    out: dict[str, float] = {}
    name = primary.get("metric")
    if isinstance(name, str) and isinstance(primary.get("value"), (int, float)):
        out[name] = float(primary["value"])
    for k, v in (extra or {}).items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[str(k)] = float(v)
    return out


def load(path: str) -> dict[str, float]:
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: expected a JSON object, got {type(doc).__name__}")
    return flatten(doc)


def compare(base: dict[str, float], cand: dict[str, float],
            tol: float, tol_overrides: dict[str, float]) -> tuple[list, list]:
    """Returns (rows, regressions). Each row:
    (section, name, base, cand, delta_frac|None, verdict)."""
    rows, regressions = [], []
    for name in sorted(set(base) | set(cand)):
        b, c = base.get(name), cand.get(name)
        d = direction(name)
        if b is None or c is None:
            rows.append((section(name), name, b, c, None,
                         "only-in-candidate" if b is None else "only-in-baseline"))
            continue
        if b == 0.0 or d == "info":
            verdict = "zero-baseline" if b == 0.0 and d != "info" else "info"
            rows.append((section(name), name, b, c, None, verdict))
            continue
        delta = (c - b) / abs(b)
        band = tol_overrides.get(name, tol)
        bad = delta < -band if d == "higher" else delta > band
        verdict = "REGRESSION" if bad else (
            "improved" if (delta > band if d == "higher" else delta < -band)
            else "ok"
        )
        row = (section(name), name, b, c, delta, verdict)
        rows.append(row)
        if bad:
            regressions.append(row)
    return rows, regressions


def report(rows: list, regressions: list, tol: float) -> None:
    by_section: dict[str, list] = {}
    for row in rows:
        by_section.setdefault(row[0], []).append(row)
    for sec in sorted(by_section):
        print(f"[{sec}]")
        for _, name, b, c, delta, verdict in by_section[sec]:
            fb = "-" if b is None else f"{b:g}"
            fc = "-" if c is None else f"{c:g}"
            fd = "" if delta is None else f" ({delta:+.1%})"
            print(f"  {verdict:>18}  {name}: {fb} -> {fc}{fd}")
    gated = [r for r in rows if r[4] is not None]
    print(
        f"\n{len(rows)} metrics, {len(gated)} gated at ±{tol:.0%}, "
        f"{len(regressions)} regression(s)"
    )
    for _, name, b, c, delta, _ in regressions:
        print(f"  REGRESSION {name}: {b:g} -> {c:g} ({delta:+.1%})")


def main(argv: list[str]) -> int:
    args: list[str] = []
    tol = DEFAULT_TOL
    tol_overrides: dict[str, float] = {}
    require_common = 0
    it = iter(argv)
    try:
        for a in it:
            if a == "--tol":
                tol = float(next(it))
            elif a == "--tol-metric":
                name, _, frac = next(it).partition("=")
                tol_overrides[name] = float(frac)
            elif a == "--require-common":
                require_common = int(next(it))
            elif a.startswith("-"):
                raise ValueError(f"unknown flag {a!r}")
            else:
                args.append(a)
    except (StopIteration, ValueError) as e:
        print(f"error: {e}\n\n{__doc__}", file=sys.stderr)
        return 2
    if len(args) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    try:
        base, cand = load(args[0]), load(args[1])
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    common_gated = [
        n for n in set(base) & set(cand)
        if base[n] != 0.0 and direction(n) != "info"
    ]
    if len(common_gated) < require_common:
        print(
            f"error: only {len(common_gated)} gateable metric(s) in common "
            f"(need {require_common}) — refusing to green-light a vacuous diff",
            file=sys.stderr,
        )
        return 2
    rows, regressions = compare(base, cand, tol, tol_overrides)
    report(rows, regressions, tol)
    return 1 if regressions else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
