"""Metrics of the nemotron_h path (blocks of one sub-layer each: grouped
Mamba-2, GQA without rotary, ungated relu2 experts of which this chip holds a
share): the reduced device trace against the counts of `lib/nh_bytes.py`, the
program's `stats.engine.startup.moe` block and its `stats.engine.moe`
counters. A reader that finds nothing to read (no trace, a configuration
without `hybrid_override_pattern`, a program without the counters or the named
kernel — the parent of the PR that brought them) returns None and the metric
is left out of the line.

The decode step's time comes from WHOLE runs of the decode program
(`readers/gdn.py whole_runs`, through `_counted` there); a kernel's events
are counted from the capture itself by `readers/ssm.py`'s counter (a process
of its own pinned to the CPU).
"""

from __future__ import annotations

from lib import nh_bytes, window
from lib.peaks import peaks_for

from readers.stats import _dig

GMM_OP = "moe_gmm"      # the routed form's kernel in a device trace


def _is_nh(ctx) -> bool:
    return "hybrid_override_pattern" in ctx.cell.config


def _step_s(ctx) -> float | None:
    """Device seconds of one decode step: the mean WHOLE run of the decode
    program ÷ `decode_block`."""
    from readers.gdn import _counted

    name = ctx.cell.config.get("decode_program")
    if not _is_nh(ctx) or not ctx.trace or not name:
        return None
    counted = _counted(ctx, name)
    if not counted or not counted["runs"] or counted["seconds"] <= 0:
        return None
    return (counted["seconds"] / counted["runs"]
            / ctx.cell.tpu["decode_block"])


def decode_hbm_share(ctx) -> float | None:
    """Bytes one decode step must move (`nh_bytes.decode_step_bytes`: the
    mixers' weights, the HELD experts the step's pairs hit at two matrices
    each, the shared expert, the head, the state of every slot read AND
    written, live K/V) ÷ the device time of one step ÷ the chip's published
    HBM bandwidth: a share of the whole decode step."""
    step_s = _step_s(ctx)
    if step_s is None:
        return None
    ph = ctx.phase
    slots, tokens = window.mean_live(ph.records, ph.w0, ph.w1)
    nbytes = nh_bytes.decode_step_bytes(ctx.cell.config, ctx.cell.tpu,
                                        tokens, slots)
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / step_s / peak


def prefill_mxu_share(ctx) -> float | None:
    """ACTIVE FLOPs prefilled per second (`nh_bytes.prefill_flops` of the
    prompts whose first token arrived in the window, with the template's
    tokens: pairs on HELD experts alone) ÷ device seconds of the prefill
    programs per second (over the capture inside it) ÷ the chip's published
    bf16 peak, as `readers/hybrid.py prefill_mxu_share` is built. Padding to
    a bucket is time spent and no work counted."""
    t = ctx.trace
    name = ctx.cell.config.get("prefill_program")
    if not _is_nh(ctx) or not t or not name or not t.get("window_s"):
        return None
    device_s = sum(v[0] for n, v in t["programs"].items() if name in n)
    if device_s <= 0:
        return None
    ph = ctx.phase
    template = int(ctx.cell.config.get("template_tokens", 0))
    flops = sum(
        nh_bytes.prefill_flops(ctx.cell.config,
                               r["prompt_tokens"] + template)
        for r in ph.records
        if r["stamps"] and ph.w0 <= r["stamps"][0][0] < ph.w1)
    if not flops:
        return None
    chips = max(1, int(ctx.device["count"] or 1))
    peak = peaks_for(ctx.device["kind"])["bf16_flops"]
    busy_share = device_s / t["window_s"]
    return 100.0 * flops / (ph.w1 - ph.w0) / chips / busy_share / peak


def held_pair_share(ctx) -> float | None:
    """(token, expert) pairs that fell on a HELD expert ÷ all the pairs the
    router made, of the growth of `stats.engine.moe.held_pairs` and
    `.pairs` over the window: held ÷ routed over under uniform routing."""
    a = _dig(ctx.phase.stats_start, "engine.moe") or {}
    b = _dig(ctx.phase.stats_end, "engine.moe") or {}
    if "held_pairs" not in b:
        return None
    pairs = b["pairs"] - a.get("pairs", 0)
    if pairs <= 0:
        return None
    return 100.0 * (b["held_pairs"] - a.get("held_pairs", 0)) / pairs


def step_roofline(ctx, op: str) -> float | None:
    """The grouped recurrence kernel against the HBM: what one pass of a
    block must move — the block's state of EVERY slot read once and written
    once (`nh_bytes.state_layer_bytes`; the decay, dt x and the G rows of B
    and C it also reads are not counted, so it cannot read over 100%) ÷ the
    mean device seconds of the capture's events of the op named `op` (one
    event a block a step) ÷ the chip's published HBM bandwidth. Bound by
    bytes: the kernel's operations (7 a state element) are under 2% of the
    chip's float32 rate at that time."""
    from readers.ssm import _counted

    if not _is_nh(ctx) or not ctx.trace:
        return None
    counted = _counted(ctx, op)
    if not counted or not counted["events"] or counted["seconds"] <= 0:
        return None
    layer_bytes = nh_bytes.state_layer_bytes(ctx.cell.config, ctx.cell.tpu)
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return (100.0 * layer_bytes * counted["events"] / counted["seconds"]
            / peak)


def expert_roofline(ctx) -> float | None:
    """The ungated expert product against the HBM, where the program ROUTES
    its decode step (`startup.moe.route.decode`; a mixture is XLA fusions
    with no name of their own in a trace: nothing is read then), as a rate
    against a rate: the bytes the grouped-matmul kernel MUST read a second —
    the growth of `stats.engine.moe.expert_hits` over the sampled window (a
    held expert that some valid pair of a forward fell on: the program
    counts them on the device, because routing by random weights is far
    from uniform and an expectation would overcount) x ONE expert's two
    matrices at the published width (`nh_bytes.expert_weight_bytes`) — ÷
    the share of the capture the kernel's events (`moe_gmm`: one a matrix,
    an expert block and a forward, decode and prefill alike) were running ÷
    the chip's published HBM bandwidth. Bound by bytes at decode: a held
    expert sees ~3 rows a step."""
    from readers.ssm import _counted
    from readers.stats import counter_share

    t = ctx.trace
    form = _dig(ctx.phase.stats_end, "engine.startup.moe.route.decode")
    if not _is_nh(ctx) or not t or not t.get("window_s") or form != "routed":
        return None
    hits = counter_share(ctx, "engine.moe.expert_hits")    # % of a second
    counted = _counted(ctx, GMM_OP)
    if (not hits or not counted or not counted["events"]
            or counted["seconds"] <= 0):
        return None
    nbytes = hits / 100.0 * nh_bytes.expert_weight_bytes(ctx.cell.config,
                                                         ctx.cell.tpu)
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / (counted["seconds"] / t["window_s"]) / peak
