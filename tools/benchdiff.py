#!/usr/bin/env python3
"""benchdiff — machine-checkable verdicts over bench.py captures.

Ten PRs of levers produced BENCH_r*.json files that were compared by
eyeballing JSON diffs in prose. This tool replaces that: it compares
two bench captures (or a series) metric by metric with per-metric
DIRECTION, minimum-effect thresholds, and noise bands, emits a markdown
delta table, and exits nonzero on regression — so the r06+ campaign and
every future PR produce comparisons a CI step can gate on.

    python tools/benchdiff.py BASE.json NEW.json [--out delta.md]
    python tools/benchdiff.py r1.json r2.json r3.json NEW.json
    python tools/benchdiff.py BASE.json NEW.json --json

Series mode (3+ files): the LAST file is the candidate; the earlier
files are repeated runs of the baseline point, and the per-metric IQR
across them becomes the noise band — the empirical answer to "is this
delta real or is this metric just loud" (the bands a single pair can
only assume, repeated smoke runs measure).

Config-fingerprint guard: bench.py stamps every capture with a
`config_fingerprint` over its RESOLVED knobs (mode, slots, clients,
buckets, quantization, …). Captures whose fingerprints disagree are
refused LOUDLY (exit 2, differing knobs listed) instead of producing a
garbage delta — a tok/s drop between a 128-slot run and a 96-slot run
is a config diff wearing a regression costume. `--force` overrides for
deliberate cross-config comparisons (e.g. a knob A/B, where the knob
ITSELF is the diff) and prints the config delta beside the table.

Verdict policy (per metric, matched on the metric's path):

  - direction: `higher` (throughput) or `lower` (latency) — only
    policied metrics can REGRESS; every other shared numeric leaf is
    reported as `info` (counters and totals scale with workload size,
    so a naive "it changed" check would cry wolf on every run).
  - min_effect: the minimum RELATIVE change worth calling real (looser
    for latency percentiles than throughput — they are noisier).
  - noise band: max(min_effect × |base|, IQR across the baseline
    series when one was given). A worse-direction delta beyond the
    band is `REGRESSED` (exit 1); a better-direction delta beyond it
    is `improved`; inside the band is `ok`.

Exit codes: 0 = no regression, 1 = regression(s), 2 = refused
(fingerprint mismatch, missing/unreadable file, unstamped capture).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Any

# (pattern over the dotted metric path, direction, min relative effect).
# First match wins; unmatched numeric leaves are informational.
POLICIES: list[tuple[re.Pattern, str, float]] = [
    (re.compile(r"(^|\.)value$"), "higher", 0.03),
    (re.compile(r"(^|\.)vs_baseline$"), "higher", 0.03),
    (re.compile(r"steady_state_tok_s$"), "higher", 0.03),
    (re.compile(r"per_slot_tok_s$"), "higher", 0.03),
    (re.compile(r"tok_s_(plain|speculative)$"), "higher", 0.05),
    (re.compile(r"(^|\.)speedup$"), "higher", 0.05),
    # Multi-turn cache-affinity payoff: turn-1 TTFT (cold prefill) over
    # turn-2+ TTFT (session lands on a member holding its radix
    # prefix). The pool-routing headline — a regression here means
    # follow-up turns stopped finding their cache.
    (re.compile(r"turn2plus_speedup$"), "higher", 0.05),
    # Autoscaler headline: SLO-attaining tokens per chip-second. A
    # regression means the controller is buying the same goodput with
    # more chips (or shedding goodput to save them).
    (re.compile(r"goodput_tokens_per_chip_s$"), "higher", 0.05),
    # symledger rollup (bench.py `ledger` block): attributed device
    # seconds per request and the wasted share are costs (lower); the
    # true-goodput headline — tokens per attributed device second —
    # must not fall.
    (re.compile(r"goodput_tokens_per_device_s$"), "higher", 0.05),
    (re.compile(r"ledger\.device_s_p\d+$"), "lower", 0.10),
    (re.compile(r"ledger\.wasted_share$"), "lower", 0.15),
    (re.compile(r"weight_stream_gbs$"), "higher", 0.05),
    (re.compile(r"acceptance_rate$"), "higher", 0.10),
    (re.compile(r"ttft[a-z0-9_]*_p\d+(_[a-z]+)?_s$"), "lower", 0.10),
    (re.compile(r"(^|\.)(mean_)?ttft_s$"), "lower", 0.10),
    (re.compile(r"e2e_p\d+_s$"), "lower", 0.10),
    (re.compile(r"inter_chunk_gap_p\d+_s$"), "lower", 0.15),
    (re.compile(r"decode_step_ms$"), "lower", 0.05),
    (re.compile(r"prefill_s_per_slot$"), "lower", 0.10),
    # Dispatch-thread wall per scheduler iteration (the pipelined-
    # scheduler target metric): host time the dispatch thread spends
    # per block after emit/bookkeep moved off-thread. Noisy like any
    # host-side latency.
    (re.compile(r"dispatch_thread_block_s\.(p50|p99)$"), "lower", 0.15),
    (re.compile(r"recovery_[a-z0-9_]*s$"), "lower", 0.15),
    (re.compile(r"wasted_tokens$"), "lower", 0.15),
]

# Stamp/bookkeeping keys excluded from metric flattening.
_META_KEYS = frozenset((
    "schema", "git_sha", "written_at", "config", "config_fingerprint",
    "metric", "unit", "metrics"))


def flatten(obj: Any, prefix: str = "") -> dict[str, float]:
    """Numeric leaves of a capture as {dotted.path: value}. Lists are
    skipped (histogram buckets/recent rings are not comparison
    targets); bools are not numbers."""
    out: dict[str, float] = {}
    if not isinstance(obj, dict):
        return out
    for key, val in obj.items():
        if not prefix and key in _META_KEYS:
            continue
        path = f"{prefix}.{key}" if prefix else str(key)
        if isinstance(val, bool):
            continue
        if isinstance(val, (int, float)):
            out[path] = float(val)
        elif isinstance(val, dict):
            out.update(flatten(val, path))
    return out


def policy_for(path: str) -> tuple[str, float] | None:
    for pat, direction, min_effect in POLICIES:
        if pat.search(path):
            return direction, min_effect
    return None


def _median(xs: list[float]) -> float:
    ys = sorted(xs)
    n = len(ys)
    mid = n // 2
    return ys[mid] if n % 2 else (ys[mid - 1] + ys[mid]) / 2.0


def _iqr(xs: list[float]) -> float:
    """Interquartile range (nearest-rank quartiles) — the robust spread
    estimate the noise bands ride; 0 for < 3 samples (no basis)."""
    if len(xs) < 3:
        return 0.0
    ys = sorted(xs)
    q1 = ys[max(0, (len(ys) + 1) // 4 - 1)]
    q3 = ys[min(len(ys) - 1, (3 * (len(ys) + 1)) // 4 - 1)]
    return max(0.0, q3 - q1)


def compare(baselines: list[dict], candidate: dict,
            min_effect_override: float | None = None) -> list[dict]:
    """Per-metric rows over the candidate vs the baseline series (last
    baseline = the reference point for deltas; the whole series feeds
    the IQR noise band). Rows: {metric, base, new, delta, delta_pct,
    band, direction, verdict}."""
    base_flat = [flatten(b) for b in baselines]
    cand_flat = flatten(candidate)
    ref = base_flat[-1]
    rows: list[dict] = []
    for path in sorted(set(ref) & set(cand_flat)):
        base_v, new_v = ref[path], cand_flat[path]
        series = [f[path] for f in base_flat if path in f]
        pol = policy_for(path)
        delta = new_v - base_v
        delta_pct = (delta / abs(base_v)) if base_v else None
        row = {"metric": path, "base": base_v, "new": new_v,
               "delta": delta, "delta_pct": delta_pct}
        if pol is None:
            row.update(direction=None, band=None, verdict="info")
            rows.append(row)
            continue
        direction, min_effect = pol
        if min_effect_override is not None:
            min_effect = min_effect_override
        # With a series, deltas anchor on the MEDIAN baseline (one
        # outlier run must not decide the reference); the printed
        # base/Δ columns still show the last baseline for readability.
        ref_point = _median(series) if len(series) >= 3 else base_v
        band = max(min_effect * abs(ref_point), _iqr(series))
        anchored = new_v - ref_point
        worse = anchored < 0 if direction == "higher" else anchored > 0
        if abs(anchored) <= band:
            verdict = "ok"
        elif worse:
            verdict = "REGRESSED"
        else:
            verdict = "improved"
        row.update(direction=direction, band=band, verdict=verdict)
        rows.append(row)
    # Policied rows first (verdicts are the point), regressions on top.
    order = {"REGRESSED": 0, "improved": 1, "ok": 2, "info": 3}
    rows.sort(key=lambda r: (order[r["verdict"]], r["metric"]))
    return rows


def _fmt(v: float | None) -> str:
    if v is None:
        return "-"
    if v == int(v) and abs(v) < 1e12:
        return str(int(v))
    return f"{v:.4g}"


def render_markdown(rows: list[dict], baselines: list[dict],
                    candidate: dict, forced_mismatch: list[str]) -> str:
    """The delta table a PR description (or a CI log) can paste."""
    base, cand = baselines[-1], candidate
    lines = ["# benchdiff", ""]
    lines.append(f"- baseline: `{base.get('config', {}).get('mode', '?')}`"
                 f" @ `{(base.get('git_sha') or 'unknown')[:12]}`"
                 + (f" (series of {len(baselines)}, IQR noise bands)"
                    if len(baselines) > 1 else ""))
    lines.append(f"- candidate: `{cand.get('config', {}).get('mode', '?')}`"
                 f" @ `{(cand.get('git_sha') or 'unknown')[:12]}`")
    if forced_mismatch:
        lines.append("- **forced cross-config comparison** — differing "
                     "knobs: " + ", ".join(
                         f"`{k}`" for k in forced_mismatch))
    n_reg = sum(1 for r in rows if r["verdict"] == "REGRESSED")
    n_imp = sum(1 for r in rows if r["verdict"] == "improved")
    lines.append(f"- verdict: "
                 + ("**REGRESSED**" if n_reg else "ok")
                 + f" ({n_reg} regressed, {n_imp} improved, "
                 f"{sum(1 for r in rows if r['verdict'] == 'ok')} within "
                 f"noise)")
    lines += ["", "| metric | base | new | Δ | Δ% | band | verdict |",
              "|---|---|---|---|---|---|---|"]
    for r in rows:
        pct = (f"{100 * r['delta_pct']:+.1f}%"
               if r["delta_pct"] is not None else "-")
        verdict = (f"**{r['verdict']}**" if r["verdict"] == "REGRESSED"
                   else r["verdict"])
        lines.append(
            f"| `{r['metric']}` | {_fmt(r['base'])} | {_fmt(r['new'])} "
            f"| {_fmt(r['delta'])} | {pct} | {_fmt(r['band'])} "
            f"| {verdict} |")
    return "\n".join(lines) + "\n"


def _load(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    if not isinstance(data, dict):
        raise ValueError(f"{path}: not a bench capture (not an object)")
    return data


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="benchdiff", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("captures", nargs="+", metavar="JSON",
                    help="bench.py captures; the LAST is the candidate, "
                         "everything before it the baseline series")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="also write the markdown delta table here")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit rows as JSON instead of markdown")
    ap.add_argument("--force", action="store_true",
                    help="compare despite fingerprint mismatch / missing "
                         "stamps (deliberate knob A/Bs)")
    ap.add_argument("--min-effect", type=float, default=None,
                    metavar="FRAC",
                    help="override every policy's minimum relative "
                         "effect (e.g. 0.05)")
    args = ap.parse_args(argv)
    if len(args.captures) < 2:
        print("benchdiff: need at least a baseline and a candidate",
              file=sys.stderr)
        return 2
    try:
        captures = [_load(p) for p in args.captures]
    except (OSError, ValueError) as exc:
        print(f"benchdiff: {exc}", file=sys.stderr)
        return 2
    baselines, candidate = captures[:-1], captures[-1]

    # ---- config-fingerprint guard (the loud refusal) ------------------
    forced_mismatch: list[str] = []
    stamps = [c.get("config_fingerprint") for c in captures]
    if any(s is None for s in stamps):
        which = [p for p, s in zip(args.captures, stamps) if s is None]
        msg = ("unstamped capture(s) (no config_fingerprint — pre-schema "
               "bench JSON?): " + ", ".join(which))
        if not args.force:
            print(f"benchdiff: REFUSING comparison — {msg}\n"
                  f"  rerun bench.py to produce stamped captures, or pass "
                  f"--force to compare anyway", file=sys.stderr)
            return 2
        print(f"benchdiff: WARNING — {msg} (forced)", file=sys.stderr)
    elif len(set(stamps)) > 1:
        # Differing knobs across the WHOLE set (a middle series file
        # can be the odd one out — diagnostics must name it, not just
        # diff endpoint configs that happen to agree).
        configs = [c.get("config") or {} for c in captures]
        all_keys = set().union(*configs)
        forced_mismatch = sorted(
            k for k in all_keys
            if len({json.dumps(cfg.get(k), sort_keys=True)
                    for cfg in configs}) > 1)
        if not args.force:
            print("benchdiff: REFUSING comparison — config fingerprints "
                  "disagree; a delta across different configs is a "
                  "config diff, not a regression.\n  differing knobs:",
                  file=sys.stderr)
            for k in forced_mismatch:
                vals = " / ".join(
                    f"{os.path.basename(p)}={cfg.get(k)!r}"
                    for p, cfg in zip(args.captures, configs))
                print(f"    {k}: {vals}", file=sys.stderr)
            print("  pass --force for a deliberate cross-config A/B",
                  file=sys.stderr)
            return 2
        knobs = ", ".join(forced_mismatch) or "<fingerprint only>"
        print("benchdiff: WARNING — cross-config comparison forced "
              f"(differing: {knobs})", file=sys.stderr)

    rows = compare(baselines, candidate,
                   min_effect_override=args.min_effect)
    regressed = [r for r in rows if r["verdict"] == "REGRESSED"]
    if args.as_json:
        print(json.dumps({
            "schema": 1,
            "regressed": bool(regressed),
            "baseline_sha": baselines[-1].get("git_sha"),
            "candidate_sha": candidate.get("git_sha"),
            "forced_mismatch": forced_mismatch,
            "rows": rows}, indent=1))
    md = render_markdown(rows, baselines, candidate, forced_mismatch)
    if not args.as_json:
        print(md, end="")
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            fh.write(md)
        print(f"[benchdiff] delta table → {args.out}", file=sys.stderr)
    return 1 if regressed else 0


if __name__ == "__main__":
    sys.exit(main())
