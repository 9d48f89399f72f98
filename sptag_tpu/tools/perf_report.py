"""Roofline perf report — `python -m sptag_tpu.tools.perf_report`.

Renders the roofline table (VERDICT §"Next round"
item 5) from a bench artifact's ledger-derived roofline block: one row
per measured kernel family (flat / dense / beam / int8) with achieved
GFLOP/s, achieved GB/s, %-of-peak on both axes and the binding resource,
plus the capability-registry peaks the percentages are stated against.

    python -m sptag_tpu.tools.perf_report BENCH_r06.json
    python -m sptag_tpu.tools.perf_report            # newest BENCH_*.json
    python -m sptag_tpu.tools.perf_report --probe    # this machine's caps

The table is plain GitHub markdown so it pastes straight into PERF.md.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import List, Optional


def _fmt(v, nd=2) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}f}"
    return str(v)


def render_peaks(peaks: dict) -> List[str]:
    out = [f"Device: **{peaks.get('device_kind', 'unknown')}** "
           f"(capability source: {peaks.get('source', 'none')})"]
    pf = peaks.get("peak_flops_f32")
    pb = peaks.get("peak_flops_bf16")
    bw = peaks.get("hbm_gbps")
    parts = []
    if pf:
        parts.append(f"f32 peak {pf / 1e12:.2f} TFLOP/s")
    if pb and pb != pf:
        parts.append(f"bf16 peak {pb / 1e12:.2f} TFLOP/s")
    if bw:
        parts.append(f"memory {bw:.1f} GB/s")
    if parts:
        out.append("Peaks: " + ", ".join(parts))
    else:
        out.append("Peaks: unknown (run with RooflineProbe=1 or on a "
                   "known TPU generation)")
    return out


def render_table(roofline: dict, qps_by_row: Optional[dict] = None
                 ) -> List[str]:
    """Markdown lines for one bench artifact's roofline block."""
    rows = roofline.get("rows", {})
    lines: List[str] = []
    lines.extend(render_peaks(roofline.get("peaks", {})))
    lines.append("")
    lines.append("| path | family | QPS | GFLOP/q | MB/q | achieved "
                 "GFLOP/s | achieved GB/s | % peak FLOPs | % peak HBM | "
                 "bound |")
    lines.append("|---|---|---|---|---|---|---|---|---|---|")
    for label in ("flat", "dense", "beam", "int8"):
        row = rows.get(label)
        if row is None:
            continue
        qps = (qps_by_row or {}).get(label)
        lines.append(
            "| {} | {} | {} | {} | {} | {} | {} | {} | {} | {} |".format(
                label, row.get("family", "-"), _fmt(qps, 1),
                _fmt(row.get("flops_per_query", 0) / 1e9, 4),
                _fmt(row.get("hbm_bytes_per_query", 0) / 1e6, 3),
                _fmt(row.get("achieved_gflops")),
                _fmt(row.get("achieved_gbps")),
                _fmt(row.get("pct_peak_flops"), 4),
                _fmt(row.get("pct_peak_hbm"), 4),
                row.get("bound", "-")))
    for label, row in sorted(rows.items()):
        if label in ("flat", "dense", "beam", "int8"):
            continue
        lines.append(
            "| {} | {} | {} | {} | {} | {} | {} | {} | {} | {} |".format(
                label, row.get("family", "-"), "-",
                _fmt(row.get("flops_per_query", 0) / 1e9, 4),
                _fmt(row.get("hbm_bytes_per_query", 0) / 1e6, 3),
                _fmt(row.get("achieved_gflops")),
                _fmt(row.get("achieved_gbps")),
                _fmt(row.get("pct_peak_flops"), 4),
                _fmt(row.get("pct_peak_hbm"), 4),
                row.get("bound", "-")))
    return lines


def report_from_bench(obj: dict) -> List[str]:
    if "parsed" in obj and isinstance(obj["parsed"], dict):
        obj = obj["parsed"]          # driver artifacts wrap the result
    roofline = obj.get("roofline")
    lines = [f"# Roofline report — platform: "
             f"{obj.get('platform', 'unknown')}", ""]
    if not roofline:
        lines.append("No roofline block in this artifact (stage failed "
                     "before any measured row; see roofline_errors).")
        errs = obj.get("roofline_errors")
        if errs:
            for k, v in errs.items():
                lines.append(f"- {k}: {v}")
        return lines
    qps_by_row = {"flat": obj.get("flat_qps"), "dense": obj.get("value"),
                  "beam": obj.get("beam_qps"), "int8": obj.get("int8_qps")}
    lines.extend(render_table(roofline, qps_by_row))
    return lines


def _newest_bench(cwd: str) -> Optional[str]:
    cands = sorted(glob.glob(os.path.join(cwd, "BENCH_*.json")),
                   key=os.path.getmtime)
    return cands[-1] if cands else None


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="perf_report",
        description="render the roofline table from a bench artifact")
    parser.add_argument("bench", nargs="?", default=None,
                        help="BENCH_*.json path (default: newest in cwd)")
    parser.add_argument("--probe", action="store_true",
                        help="ignore artifacts; print THIS machine's "
                             "capability (runs the disk-cached micro-"
                             "probe on non-TPU backends)")
    parser.add_argument("--platform", default=None,
                        help="pin the jax platform first (e.g. cpu)")
    args = parser.parse_args(argv)

    if args.probe:
        from sptag_tpu.utils import pin_platform, roofline

        pin_platform(args.platform)
        cap = roofline.capability(probe=True)
        print("\n".join(render_peaks({
            "device_kind": cap.device_kind, "source": cap.source,
            "peak_flops_f32": cap.peak_flops_f32,
            "peak_flops_bf16": cap.peak_flops_bf16,
            "hbm_gbps": cap.hbm_gbps})))
        return 0

    path = args.bench or _newest_bench(os.getcwd())
    if path is None or not os.path.exists(path):
        print("perf_report: no bench artifact found (pass a "
              "BENCH_*.json path)", file=sys.stderr)
        return 2
    with open(path) as f:
        obj = json.load(f)
    print("\n".join(report_from_bench(obj)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
