#!/usr/bin/env python3
"""symtop — live terminal fleet view over the telemetry layer.

Polls one-or-many providers and renders a per-provider, per-tier table:
tok/s, TTFT p50/p99, queue depth, in-flight, occupancy, shed count,
handoff-link health, and — on autoscaled pools — TARGET (the
controller's desired M×N vs the live topology, from
sym_autoscale_target_members) and SCALE (booked scaling decisions per
minute) — the operator's answer to "is the fleet healthy RIGHT NOW",
where `benchmarks/run.py` answers "how fast was it over a run".

Two poll paths, mixable in one invocation:

  --metrics-url http://host:port/metrics     the Prometheus exposition
        endpoint (`metrics.port` in provider.yaml) — no keys, no swarm
        stack, works against anything that speaks the text format
  --provider tcp://host:port [--key HEX]     the peer wire: one metrics
        probe per poll (MessageKey.METRICS reply = stats snapshot + the
        tier-labeled registry snapshots), Noise-encrypted like any
        client — the swarm path, no open port required

Rates (tok/s, shed/s) are counter deltas between polls; the first
sample (and --once) falls back to lifetime averages over the provider's
reported uptime. Disagg providers show one sub-row per engine tier
(prefill / decode) from the `tier` label the telemetry layer carries
end to end.

Run:
    python tools/symtop.py --metrics-url http://127.0.0.1:9100/metrics
    python tools/symtop.py --provider tcp://127.0.0.1:4631 --once
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import sys
import time
import urllib.request
from typing import Any

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from symmetry_tpu.utils.metrics import (  # noqa: E402
    histogram_quantile,
    parse_prometheus_text,
)

COLUMNS = ("PROVIDER", "TIER", "TOK/S", "TTFT p50", "TTFT p99",
           "QUEUE", "INFL", "OCC", "DEPTH", "SHED", "RESUME",
           "WASTED", "REUSED", "DUMPS", "COST", "WASTE%", "GPUT",
           "LINK", "STATE", "SHARE", "HIT", "TARGET", "SCALE",
           "RECUR", "DSA", "AHEAD", "STALLS", "TAIL")
WIDTHS = (22, 10, 9, 9, 9, 7, 6, 5, 5, 7, 7, 7, 7, 6, 7, 6, 7, 6,
          9, 6, 6, 9, 6, 20, 18, 11, 9, 6)

# sym_pool_member_state gauge encoding (engine/disagg/pool.py
# STATE_CODES) rendered back to the membership lifecycle names.
POOL_STATE_NAMES = {0: "joining", 1: "healthy", 2: "draining", 3: "lost"}


# ----------------------------------------------------- family flattening


def families_from_snapshots(snaps: list[dict]) -> dict[str, dict]:
    """Registry snapshots (the wire `metrics.snapshots` shape) → the
    same family dict parse_prometheus_text produces, extra labels
    (tier) stamped — one row builder then serves both poll paths."""
    fams: dict[str, dict] = {}
    for item in snaps or []:
        snap = item.get("snapshot") or {}
        extra = dict(item.get("labels") or {})
        for name, fam in (snap.get("families") or {}).items():
            out = fams.setdefault(
                name, {"kind": fam.get("kind", "untyped"), "series": []})
            for s in fam.get("series") or []:
                labels = {**(s.get("labels") or {}), **extra}
                if fam.get("kind") == "histogram":
                    for le, c in s.get("buckets") or []:
                        out["series"].append(
                            {"labels": {**labels, "le": str(le)},
                             "value": float(c), "suffix": "_bucket"})
                    out["series"].append({"labels": labels,
                                          "value": float(s.get("sum", 0.0)),
                                          "suffix": "_sum"})
                    out["series"].append({"labels": labels,
                                          "value": float(s.get("count", 0)),
                                          "suffix": "_count"})
                else:
                    out["series"].append({"labels": labels,
                                          "value": float(s.get("value", 0.0)),
                                          "suffix": ""})
    return fams


def _value(fams: dict, name: str, default: float | None = None,
           **labels: str) -> float | None:
    """Sum of matching plain samples (counters sum across label sets)."""
    fam = fams.get(name)
    if fam is None:
        return default
    total, hit = 0.0, False
    for s in fam["series"]:
        if s.get("suffix"):
            continue
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            total += s["value"]
            hit = True
    return total if hit else default


def _quantile(fams: dict, name: str, q: float,
              **labels: str) -> float | None:
    fam = fams.get(name)
    if fam is None:
        return None
    buckets: dict[float | str, float] = {}
    for s in fam["series"]:
        if s.get("suffix") != "_bucket":
            continue
        lab = dict(s["labels"])
        le = lab.pop("le", None)
        if le is None or not all(lab.get(k) == v
                                 for k, v in labels.items()):
            continue
        buckets[le] = buckets.get(le, 0.0) + s["value"]

    def _key(le: str) -> float:
        return float("inf") if le == "+Inf" else float(le)

    ordered = sorted(buckets.items(), key=lambda kv: _key(kv[0]))
    return histogram_quantile([(le, c) for le, c in ordered], q)


def _ledger_cost(fams: dict) -> tuple[float | None, float]:
    """(total attributed device seconds, finished-request count) from
    the sym_request_device_seconds histogram. The count is the largest
    per-phase observation count — every finished request observes each
    phase it ran, so the busiest phase (decode for almost all traffic)
    counts the requests."""
    fam = fams.get("sym_request_device_seconds")
    if fam is None:
        return None, 0.0
    total = 0.0
    counts: dict[str, float] = {}
    for s in fam["series"]:
        if s.get("suffix") == "_sum":
            total += s["value"]
        elif s.get("suffix") == "_count":
            phase = s["labels"].get("phase", "")
            counts[phase] = counts.get(phase, 0.0) + s["value"]
    return total, max(counts.values(), default=0.0)


def _tiers(fams: dict) -> list[str]:
    seen: list[str] = []
    fam = fams.get("sym_sched_occupancy") or {"series": []}
    for s in fam["series"]:
        tier = s["labels"].get("tier", "")
        if tier and tier not in seen:
            seen.append(tier)
    return seen


def _pool_rows(name: str, fams: dict) -> list[dict[str, Any]]:
    """One sub-row per elastic-pool member (disagg M×N providers):
    membership state (joining/healthy/draining/lost), link health
    derived from it, the member's share of its tier's lifetime
    placements, and HIT — the radix-cache blocks affinity placement
    predicted it would reuse there (a warm pool shows HIT climbing on
    the members sessions keep landing on; all-zero HIT under multi-turn
    load means gossip isn't arriving) — the live answer to 'who is
    taking the traffic, who just churned, and is the cache-affine
    router actually finding warm members'."""
    fam = fams.get("sym_pool_member_state")
    if fam is None:
        return []
    states: dict[tuple[str, str], float] = {}
    for s in fam["series"]:
        if s.get("suffix"):
            continue
        lab = s["labels"]
        node = lab.get("node", "")
        if node:
            states[(lab.get("tier", ""), node)] = s["value"]
    if not states:
        return []
    placements: dict[tuple[str, str], float] = {}
    totals: dict[str, float] = {}
    pfam = fams.get("sym_pool_placements_total") or {"series": []}
    for s in pfam["series"]:
        if s.get("suffix"):
            continue
        lab = s["labels"]
        key = (lab.get("tier", ""), lab.get("node", ""))
        placements[key] = placements.get(key, 0.0) + s["value"]
        totals[key[0]] = totals.get(key[0], 0.0) + s["value"]
    hits: dict[tuple[str, str], float] = {}
    hfam = fams.get("sym_pool_predicted_hit_blocks") or {"series": []}
    for s in hfam["series"]:
        if s.get("suffix"):
            continue
        lab = s["labels"]
        key = (lab.get("tier", ""), lab.get("node", ""))
        hits[key] = hits.get(key, 0.0) + s["value"]
    rows: list[dict[str, Any]] = []
    for (tier, node), code in sorted(states.items()):
        total = totals.get(tier, 0.0)
        share = (placements.get((tier, node), 0.0) / total
                 if total else None)
        state = POOL_STATE_NAMES.get(int(code), "?")
        rows.append({
            "provider": name, "tier": node, "tok_s": None,
            "ttft_p50": None, "ttft_p99": None, "queue": None,
            "in_flight": None, "occupancy": None, "shed": None,
            # membership IS link health: healthy/draining members hold
            # a live link; lost means the link (or node) is gone.
            "link": ("up" if state in ("healthy", "draining")
                     else "DOWN" if state == "lost" else "-"),
            "state": state,
            "share": f"{share * 100:.0f}%" if share is not None else None,
            "hit": hits.get((tier, node)),
        })
    return rows


# ------------------------------------------------------------- row model


def read_tail(engine: dict | None
              ) -> tuple[str | None, str | None, float | None]:
    """The engine host's flush-ahead counter, stall and read records (the
    stats reply's `engine` block; a wire poll has it, a Prometheus scrape
    does not): AHEAD = decode blocks whose events left with an admission
    still unread behind them / the seconds of those admissions' waits
    (what the clients no longer wait; lifetime totals);
    STALLS = count / longest excess in seconds; TAIL = 99th percentile of
    the decode-block read-to-read intervals among the last 64 reads —
    the engine's side of the clients' inter-chunk gap p99. One reply's
    `recent` is consecutive reads by construction, so only an idle
    boundary (`caused_by`) breaks the chain here; the benchmark's
    `readers/tail.py intervals` unions many replies and checks `seq` too."""
    engine = engine or {}
    stalls, reads = engine.get("stalls"), engine.get("reads")
    ahead = engine.get("flush_ahead")
    ahead_cell = (None if not ahead else
                  f"{ahead['blocks']}/{ahead['lead_s']:.1f}")
    stall_cell = (None if not stalls else
                  f"{stalls['count']}/{stalls['longest_s']:.1f}")
    if not reads:
        return ahead_cell, stall_cell, None
    recs = [dict(zip(reads["fields"], row)) for row in reads["recent"]]
    blocks = [r for r in recs if r["kind"] in ("decode_block", "verify")]
    ivs = sorted(b["t"] - a["t"] for a, b in zip(blocks, blocks[1:])
                 if b["caused_by"] == a["seq"])
    tail = ivs[math.ceil(0.99 * len(ivs)) - 1] if ivs else None
    return ahead_cell, stall_cell, tail


def read_dsa(engine: dict | None) -> str | None:
    """Learned sparse attention (the stats reply's `engine.dsa` counters
    and `startup.attention.sparse`; None for any other model): DSA =
    selected / candidates since start as a percentage — how sparse the
    attention ran — the decode program's form (`masked`, or `gather` once
    one is built) and what makes its selection (`/kernel`: dsa_select;
    `/xla`)."""
    dsa = (engine or {}).get("dsa")
    if not dsa or not dsa.get("candidates"):
        return None
    sparse = (((engine.get("startup") or {}).get("attention") or {})
              .get("sparse") or {})
    form = (sparse.get("form") or {}).get("decode", "").split(" ")[0]
    select = (sparse.get("select") or {}).get("decode", "").split(" ")[-1]
    return (f"{100.0 * dsa['selected'] / dsa['candidates']:.0f}% {form}"
            + (f"/{select}" if select else "")).strip()


def read_recurrent(engine: dict | None) -> str | None:
    """A model with recurrent layers (the stats reply's `startup.ssm`; None
    for any other): RECUR = the kind — mamba2, gated_deltanet, short_conv —
    and what all slots' state holds, in MiB."""
    ssm = ((engine or {}).get("startup") or {}).get("ssm") or {}
    if not ssm.get("kind"):
        return None
    return f"{ssm['kind']} {ssm.get('state_bytes', 0) / 2 ** 20:.0f}M"


def build_rows(name: str, fams: dict, prev: dict | None, now: float,
               engine: dict | None = None) -> list[dict[str, Any]]:
    """One provider-level row plus one sub-row per engine tier. `prev`
    is the previous poll's {"t", "tok", "shed"} for rate deltas;
    `engine` the stats reply's engine block, where the poll has one."""
    ahead_cell, stall_cell, tail = read_tail(engine)
    tok = _value(fams, "sym_provider_tokens_out_total", 0.0)
    shed = _value(fams, "sym_provider_sheds_total", 0.0)
    cost_total, cost_n = _ledger_cost(fams)
    wasted_s = _value(fams, "sym_request_wasted_seconds")
    uptime = _value(fams, "sym_provider_uptime_seconds")
    decisions = _value(fams, "sym_autoscale_decisions_total")
    if prev and now > prev["t"]:
        dt = now - prev["t"]
        tok_s = max(tok - prev["tok"], 0.0) / dt
        # SHED as a rate too (sheds since the last poll): a provider
        # that shed 10k requests last week but is healthy now must not
        # look like one actively shedding. --once / the first poll fall
        # back to the lifetime total.
        shed_disp = max(shed - prev["shed"], 0.0) / dt
        # SCALE: autoscale decisions per MINUTE since the last poll
        # (spawns + drains + rebalances — holds are not booked in the
        # counter). A fleet that keeps flapping shows it here.
        scale_disp = (None if decisions is None else
                      max(decisions - prev.get("dec", 0.0), 0.0)
                      * 60.0 / dt)
    else:
        tok_s = tok / max(uptime, 1e-9) if uptime else None
        shed_disp = shed
        scale_disp = decisions  # lifetime total on the first poll
    link = _value(fams, "sym_link_connected")
    # TARGET: the autoscaler's desired topology vs what is live —
    # "live MxN>target MxN" while a decision is being actuated (or a
    # member is mid-join/drain), collapsing to one MxN at steady state.
    target = None
    tgt_p = _value(fams, "sym_autoscale_target_members", tier="prefill")
    tgt_d = _value(fams, "sym_autoscale_target_members", tier="decode")
    if tgt_p is not None or tgt_d is not None:
        live: dict[str, int] = {}
        for s in (fams.get("sym_pool_member_state")
                  or {"series": []})["series"]:
            if not s.get("suffix") and s["value"] == 1:  # healthy
                tier = s["labels"].get("tier", "")
                live[tier] = live.get(tier, 0) + 1
        live_mn = f"{live.get('prefill', 0):.0f}x{live.get('decode', 0):.0f}"
        tgt_mn = f"{tgt_p or 0:.0f}x{tgt_d or 0:.0f}"
        target = tgt_mn if live_mn == tgt_mn else f"{live_mn}>{tgt_mn}"
    rows = [{
        "provider": name, "tier": "",
        "tok_s": tok_s,
        "ttft_p50": _quantile(fams, "sym_provider_ttft_seconds", 0.50),
        "ttft_p99": _quantile(fams, "sym_provider_ttft_seconds", 0.99),
        "queue": _value(fams, "sym_provider_pending_first_token"),
        "in_flight": _value(fams, "sym_provider_in_flight"),
        "occupancy": None,
        "shed": shed_disp,
        # Stream-resumption health (PR-14 families, lifetime totals):
        # resumes served, overlap tokens the relay's dedup DROPPED
        # (work the engine redid — should stay near zero), and the
        # flight-recorder dump count (any nonzero DUMPS is a provider
        # with post-mortem evidence waiting to be read).
        "resume": _value(fams, "sym_resume_requests_total"),
        "wasted": _value(fams, "sym_resume_wasted_tokens_total"),
        "reused": None,
        "dumps": _value(fams, "sym_provider_flight_dumps_total"),
        # symledger attribution (tpu.ledger families): COST = mean
        # attributed device seconds per finished request, WASTE% =
        # share of device time spent on work no client kept (rejected
        # drafts, sheds, kills, resume overlap), GPUT = the windowed
        # SLO-goodput gauge — attaining tokens per device second, the
        # honest throughput headline.
        "cost": (cost_total / cost_n if cost_total is not None and cost_n
                 else None),
        "waste": (_fmt_pct(wasted_s / (cost_total + wasted_s))
                  if wasted_s is not None and cost_total
                  else None),
        "gput": _value(fams, "sym_goodput_tokens_per_device_second"),
        "link": (None if link is None else ("up" if link else "DOWN")),
        "state": None, "share": None,
        "target": target, "scale": scale_disp,
        "ahead": ahead_cell, "stalls": stall_cell, "tail": tail,
        "recur": read_recurrent(engine), "dsa": read_dsa(engine),
        "_sample": {"t": now, "tok": tok, "shed": shed or 0.0,
                    "dec": decisions or 0.0},
    }]
    for tier in _tiers(fams):
        rows.append({
            "provider": name, "tier": tier,
            "state": None, "share": None,
            "tok_s": None,
            # True engine-side TTFT (enqueue → first sampled token),
            # not dispatch wall — queue wait must show under overload.
            "ttft_p50": _quantile(fams, "sym_sched_ttft_seconds", 0.50,
                                  tier=tier),
            "ttft_p99": _quantile(fams, "sym_sched_ttft_seconds", 0.99,
                                  tier=tier),
            "queue": _value(fams, "sym_sched_queue_depth", tier=tier),
            "in_flight": None,
            "occupancy": _value(fams, "sym_sched_occupancy", tier=tier),
            # Live pipeline depth (blocks in flight after the last
            # scheduler iteration): 0 = idle tier, steady < configured
            # depth = the pipeline never fills (admission-bound).
            "depth": _value(fams, "sym_sched_pipeline_depth", tier=tier),
            "shed": _value(fams, "sym_sched_deadline_sheds_total",
                           tier=tier),
            # Scheduler-side resume admissions and the radix tokens
            # they reused instead of re-prefilling (reused > 0 is the
            # cheap-resume contract; 0 with RESUME > 0 means resumes
            # are paying full prefills — cache too small or misses).
            "resume": _value(fams, "sym_resume_admissions_total",
                             tier=tier),
            "wasted": None,
            "reused": _value(fams, "sym_resume_reused_tokens_total",
                             tier=tier),
            "dumps": None,
            "link": None,
        })
    rows.extend(_pool_rows(name, fams))
    return rows


def _fmt_pct(v: float | None) -> str | None:
    return None if v is None else f"{v * 100:.0f}%"


def _fmt_cell(v: Any, width: int) -> str:
    if v is None:
        s = "-"
    elif isinstance(v, float):
        s = f"{v:.2f}" if v < 100 else f"{v:.0f}"
    else:
        s = str(v)
    return s[:width].ljust(width)


def render_table(rows: list[dict[str, Any]]) -> str:
    out = ["  ".join(c.ljust(w) for c, w in zip(COLUMNS, WIDTHS))]
    for r in rows:
        cells = (r["provider"], r["tier"] or "-", r["tok_s"],
                 r["ttft_p50"], r["ttft_p99"], r["queue"], r["in_flight"],
                 r["occupancy"], r.get("depth"),
                 r["shed"], r.get("resume"),
                 r.get("wasted"), r.get("reused"), r.get("dumps"),
                 r.get("cost"), r.get("waste") or "-", r.get("gput"),
                 r["link"] or "-",
                 r.get("state") or "-", r.get("share") or "-",
                 r.get("hit"), r.get("target") or "-", r.get("scale"),
                 r.get("recur") or "-", r.get("dsa") or "-",
                 r.get("ahead") or "-", r.get("stalls") or "-",
                 r.get("tail"))
        out.append("  ".join(_fmt_cell(c, w)
                             for c, w in zip(cells, WIDTHS)))
    return "\n".join(out)


# ----------------------------------------------------------- poll sources


def poll_http(url: str, timeout: float = 5.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return parse_prometheus_text(resp.read().decode("utf-8"))


async def poll_wire(address: str, key_hex: str | None
                    ) -> tuple[dict, dict | None]:
    """One metrics probe over the peer wire (stats + tier-labeled
    registry snapshots ride the same reply): the families, and the
    engine host's stats block."""
    from symmetry_tpu.client.client import SymmetryClient

    client = SymmetryClient()
    key = bytes.fromhex(key_hex) if key_hex else None
    session = await client.connect_direct(address, provider_key=key)
    try:
        stats = await session.stats()
    finally:
        await session.close()
    return families_from_snapshots(
        (stats.get("metrics") or {}).get("snapshots") or []), stats.get(
            "engine")


# ------------------------------------------------------------------ main


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="symtop", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--metrics-url", action="append", default=[],
                    metavar="URL",
                    help="Prometheus exposition endpoint to poll "
                         "(repeatable)")
    ap.add_argument("--provider", action="append", default=[],
                    metavar="ADDR",
                    help="provider address to poll over the peer wire "
                         "(repeatable; tcp://host:port)")
    ap.add_argument("--key", action="append", default=[], metavar="HEX",
                    help="expected provider public key for the matching "
                         "--provider (positional pairing; optional)")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="poll interval seconds (default 2)")
    ap.add_argument("--once", action="store_true",
                    help="render one table and exit (CI / scripts)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit rows as JSON lines instead of the table")
    args = ap.parse_args(argv)
    if not args.metrics_url and not args.provider:
        ap.error("give at least one --metrics-url or --provider")

    targets: list[tuple[str, str, str | None]] = []
    for url in args.metrics_url:
        targets.append(("http", url, None))
    for i, addr in enumerate(args.provider):
        targets.append(("wire", addr,
                        args.key[i] if i < len(args.key) else None))

    prev: dict[str, dict] = {}
    loop = asyncio.new_event_loop()
    try:
        while True:
            now = time.monotonic()
            rows: list[dict[str, Any]] = []
            for kind, where, key in targets:
                short = where.split("//")[-1]
                try:
                    fams, engine = (
                        (poll_http(where), None) if kind == "http"
                        else loop.run_until_complete(asyncio.wait_for(
                            poll_wire(where, key), 10.0)))
                except Exception as exc:  # noqa: BLE001 — show, keep polling
                    rows.append({"provider": short, "tier": "",
                                 "tok_s": None, "ttft_p50": None,
                                 "ttft_p99": None, "queue": None,
                                 "in_flight": None, "occupancy": None,
                                 "shed": None,
                                 "link": f"ERR:{type(exc).__name__}"})
                    continue
                target_rows = build_rows(short, fams, prev.get(where), now,
                                         engine)
                sample = target_rows[0].pop("_sample", None)
                if sample:
                    prev[where] = sample
                rows.extend(target_rows)
            if args.as_json:
                print(json.dumps(rows))
            else:
                if not args.once:
                    sys.stdout.write("\x1b[2J\x1b[H")  # clear + home
                    print(f"symtop — {len(targets)} target(s), every "
                          f"{args.interval:.0f}s — "
                          f"{time.strftime('%H:%M:%S')}\n")
                print(render_table(rows))
            if args.once:
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0
    finally:
        loop.close()


if __name__ == "__main__":
    sys.exit(main())
