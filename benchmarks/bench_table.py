"""Render the README benchmark tables from the ``BENCH_*.json`` files that
are present (``BENCH_store.json`` / ``BENCH_export.json`` /
``BENCH_kernels.json`` / ``BENCH_fleet.json``).

    PYTHONPATH=src python -m benchmarks.bench_table [DIR]

Prints GitHub-flavored markdown. The tables embedded in README.md are the
output of this script over the checked-in ``BENCH_*.json``; re-run
``make bench`` followed by this module to refresh them after a change to
the store, export or fleet hot paths.
"""
from __future__ import annotations

import json
import os
import sys


def render_store(bench: dict) -> str:
    w = bench["wado"]
    return "\n".join([
        f"Frame-level WADO ({w['n_frames']}-frame encapsulated instance, "
        f"{w['instance_bytes']:,} bytes):",
        "",
        "| path | µs/frame fetch | vs reparse |",
        "|---|---|---|",
        f"| reparse per fetch (seed) | {w['reparse_us_per_frame']:,.0f} | "
        "1× |",
        f"| `Part10Index` (cached) | {w['indexed_us_per_frame']:.2f} | "
        f"{w['indexed_speedup']:,.0f}× |",
        f"| store service (`retrieve_frame`) | "
        f"{w['store_us_per_frame']:.2f} | {w['store_speedup']:,.0f}× |",
        "",
        f"Frames byte-identical across all paths "
        f"(asserted in the run: {w['bytes_identical']}).",
    ])


def render_export(bench: dict) -> str:
    d = bench["decode"]
    e = bench["export"]
    lines = [
        f"Whole-level JPEG decode ({d['n_tiles']} tiles of {d['tile']}², "
        f"a {d['hw']}×{d['hw']} level):",
        "",
        "| path | decode (µs/tile) | vs per-tile |",
        "|---|---|---|",
        f"| per-tile loop (seed) | {d['per_tile_us']:,.0f} | 1.00× |",
        f"| batched (`decode_tiles_batch`) | {d['batched_us']:,.0f} | "
        f"{d['speedup']:.2f}× |",
        "",
        "Batch scaling (the lockstep entropy decoder amortizes across "
        "tiles): "
        + ", ".join(f"{s['speedup']:.2f}× at n={s['n_tiles']}"
                    for s in d["batch_scaling"])
        + f". Pixel-identical to the per-tile loop and coefficient-exact "
        f"round-trip asserted in the run: {d['pixel_identical']} / "
        f"{d['coef_roundtrip_exact']}.",
        "",
        f"Study export ({e['slide_hw']}² slide → "
        f"{e['levels_exported']}-level tiled-TIFF pyramid, "
        f"{e['frames_decoded']} frames over WADO):",
        "",
        "| metric | value |",
        "|---|---|",
        f"| export wall (s) | {e['export_s']:.3f} |",
        f"| throughput (MPix/s) | {e['mpix_s']:.2f} |",
        f"| repeated export byte-identical | {e['repeat_identical']} |",
        f"| export after crash + `rebuild_index()` byte-identical | "
        f"{e['rebuild_identical']} |",
        f"| exported TIFFs reopen via `open_slide` | "
        f"{e['reopens_via_sniffer']} |",
    ]
    return "\n".join(lines)


def render_kernels(bench: dict) -> str:
    rb = bench["roofline_batch"]
    lines = [
        f"Kernel roofline ({rb['n_tiles']}-tile level batch of "
        f"{rb['tile']}² tiles, {bench['hw']['name']} targets; terms from "
        f"the SPMD-partitioned HLO via `roofline.analyze_hlo` + "
        f"`derive_terms`):",
        "",
        "| kernel | devices | bound | compute µs | memory µs | "
        "collective µs | mfu bound |",
        "|---|---|---|---|---|---|---|",
    ]
    for kernel, per_d in bench["roofline"].items():
        for d, t in sorted(per_d.items(), key=lambda kv: int(kv[0])):
            lines.append(
                f"| `{kernel}` | {d} | "
                f"{t['dominant'].replace('_s', '')} | "
                f"{t['compute_s']*1e6:.1f} | {t['memory_s']*1e6:.1f} | "
                f"{t['collective_s']*1e6:.1f} | {t['mfu_bound']:.4f} |")
    scaling = bench.get("batch_scaling")
    if scaling:
        lines += [
            "",
            "Batch scaling (fused transform dispatch, µs/tile — flat "
            "across batch sizes, no small-batch recompile cliff; "
            "asserted in the run): "
            + ", ".join(f"{s['transform_us_per_tile']:,.0f} at "
                        f"n={s['n_tiles']}" for s in scaling) + ".",
        ]
    return "\n".join(lines)


def render_fleet(bench: dict) -> str:
    f2 = bench["fig2"]
    f3 = bench["fig3"]
    fi = bench["fault_injection"]
    bp = bench["backpressure"]
    by_n: dict[int, dict[str, float]] = {}
    for r in f2["rows"]:
        if r["workflow"] != "calibration":
            by_n.setdefault(r["n"], {})[r["workflow"]] = r["seconds"]
    lines = [
        f"Figure 2 — batch completion time (τ={f2['tau_s']:.0f} s/slide, "
        f"cold start {f2['cold_start_s']:.0f} s; simulated fleet with "
        "per-instance queues + controller scaling):",
        "",
        "| n slides | serial (s) | 16-way parallel (s) | "
        "event-driven fleet (s) |",
        "|---|---|---|---|",
    ]
    for n in sorted(by_n):
        t = by_n[n]
        lines.append(f"| {n} | {t['serial']:,.0f} | {t['parallel16']:,.0f} |"
                     f" {t['event_driven_fleet']:,.0f} |")
    lines += [
        "",
        "Cold start makes the fleet lose at n=1 and win at n≥10 "
        "(asserted in the run: "
        + ", ".join(f"{k}={v}" for k, v in f2["crossover"].items()) + ").",
        "",
        f"Figure 3 — avg container instances per minute, {f3['n_slides']}-"
        f"slide burst (peak {f3['peak_avg_instances']:.0f}, instantaneous "
        f"max {f3['peak_instantaneous']:.0f} ≤ max_instances="
        f"{f3['max_instances']}, decays to zero: {f3['decays_to_zero']}):",
        "",
        "| minute | " + " | ".join(str(m) for m, _ in f3["minutes"]) + " |",
        "|---|" + "---|" * len(f3["minutes"]),
        "| instances | "
        + " | ".join(f"{v:.0f}" for _, v in f3["minutes"]) + " |",
        "",
        f"Fault-injection gauntlet ({fi['n_slides']} real conversions under "
        f"`SimScheduler`, {fi['n_shards']}-shard store): "
        + "/".join(f"{v} {k}" for k, v in
                   sorted(fi["faults_injected"].items()))
        + " deliveries faulted, 1 instance kill, 1 shard crash → "
        f"{fi['dead_lettered']} dead-lettered, "
        f"{fi['study_tar_writes']} study-tar writes "
        f"(one per slide), byte-identical to a serial conversion: "
        f"{fi['byte_identical_to_serial']}; crash + `rebuild_index()` "
        f"QIDO/WADO identical: {fi['crash_rebuild_identical']}. "
        f"Backpressure: {bp['shed']} sheds → {bp['budget_exempt_requeues']} "
        f"budget-exempt requeues, {bp['completed']}/{bp['n_slides']} "
        f"completed, {bp['dead_lettered']} dead-lettered.",
    ]
    return "\n".join(lines)


def main() -> None:
    base = sys.argv[1] if len(sys.argv) > 1 else "."
    tables = []
    for name, renderer in (("BENCH_store.json", render_store),
                           ("BENCH_export.json", render_export),
                           ("BENCH_kernels.json", render_kernels),
                           ("BENCH_fleet.json", render_fleet)):
        path = os.path.join(base, name)
        if os.path.exists(path):
            with open(path) as f:
                tables.append(renderer(json.load(f)))
    print("\n\n".join(tables))


if __name__ == "__main__":
    main()
