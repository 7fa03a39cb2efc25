"""Render EXPERIMENTS.md tables from the dry-run / hillclimb JSON records.

  PYTHONPATH=src python -m repro.analysis.report --inject
"""
from __future__ import annotations

import argparse
import json
import os

RESULTS = "results"
SHAPE_ORDER = {"train_4k": 0, "prefill_32k": 1, "decode_32k": 2, "long_500k": 3}


def _load(path: str) -> list[dict]:
    full = os.path.join(RESULTS, path)
    if not os.path.exists(full):
        return []
    return [json.loads(l) for l in open(full) if l.strip()]


def _fmt_s(x: float) -> str:
    if x >= 1.0:
        return f"{x:8.2f} s"
    return f"{x*1e3:7.2f} ms"


def roofline_table() -> str:
    recs = _load("dryrun_single.json")
    lines = [
        "| arch | shape | compute | memory | collective | dominant | useful |",
        "|---|---|---:|---:|---:|---|---:|",
    ]
    for r in sorted(recs, key=lambda r: (r["arch"], SHAPE_ORDER.get(r["shape"], 9))):
        if r["status"] == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                         f"*skipped: sub-quadratic path required* | — |")
            continue
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | ERROR | | | | |")
            continue
        t = r["roofline"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {_fmt_s(t['compute_s'])} | "
            f"{_fmt_s(t['memory_s'])} | {_fmt_s(t['collective_s'])} | "
            f"**{t['dominant']}** | {(r['useful_flops_ratio'] or 0):.3f} |")
    return "\n".join(lines)


def hillclimb_table() -> str:
    recs = _load("hillclimb.json")
    lines = [
        "| pair | variant | compute | memory | collective | dominant | useful |",
        "|---|---|---:|---:|---:|---|---:|",
    ]
    for r in recs:
        if r.get("status") not in (None, "ok"):
            lines.append(f"| {r.get('pair','?')} | {r.get('variant','?')} | "
                         f"ERROR {r.get('error','')[:40]} | | | | |")
            continue
        t = r["roofline"]
        tag = r.get("tag", "")
        pair = tag.split(":")[0] if ":" in tag else r["arch"]
        lines.append(
            f"| {pair} | {r.get('variant','?')} | {_fmt_s(t['compute_s'])} | "
            f"{_fmt_s(t['memory_s'])} | {_fmt_s(t['collective_s'])} | "
            f"{t['dominant']} | {(r['useful_flops_ratio'] or 0):.3f} |")
    return "\n".join(lines)


def telemetry_table(path: str) -> str:
    """Render a per-step table from a telemetry JSONL stream
    (``core/telemetry.py`` schema: one ``compile`` record, then ``step``
    records carrying tokens/s and MFU)."""
    recs = [json.loads(l) for l in open(path) if l.strip()]
    head = next((r for r in recs if r.get("kind") == "compile"), None)
    lines = []
    if head is not None:
        lines.append(
            f"telemetry: {head.get('arch','?')} plan={head.get('plan')} "
            f"gb={head.get('global_batch')} seq={head.get('seq_len')} "
            f"devices={head.get('devices')} backend={head.get('backend')}")
        lines.append("")
    lines += [
        "| step | wall | tokens/s | TFLOP/s/dev | MFU | loss |",
        "|---:|---:|---:|---:|---:|---:|",
    ]
    for r in recs:
        if r.get("kind") != "step":
            continue
        loss = r.get("loss")
        mfu = "not measured" if r["mfu"] is None else f"{r['mfu'] * 100:.2f}%"
        lines.append(
            f"| {r['step']} | {_fmt_s(r['wall_s'])} | "
            f"{r['tokens_per_s']:,.0f} | {r['tflops_per_device']:.3f} | "
            f"{mfu} | "
            f"{'—' if loss is None else f'{loss:.4f}'} |")
    return "\n".join(lines)


def inject() -> None:
    with open("EXPERIMENTS.md") as f:
        text = f.read()
    text = text.replace("<!-- ROOFLINE_TABLE -->", roofline_table())
    text = text.replace("<!-- HILLCLIMB_TABLE -->", hillclimb_table())
    with open("EXPERIMENTS.md", "w") as f:
        f.write(text)
    print("EXPERIMENTS.md tables injected")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--inject", action="store_true")
    ap.add_argument("--telemetry", metavar="JSONL", default=None,
                    help="render a step/MFU table from a telemetry "
                         "JSONL (launch/train.py --log-jsonl output)")
    args = ap.parse_args()
    if args.telemetry:
        print(telemetry_table(args.telemetry))
    elif args.inject:
        inject()
    else:
        print(roofline_table())
        print()
        print(hillclimb_table())


if __name__ == "__main__":
    main()
