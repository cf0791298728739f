"""Aggregate dry-run artifacts into the §Roofline table (CSV + markdown)."""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

ART = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "artifacts", "dryrun")

COLS = ("arch", "shape", "mesh", "kind", "bound", "compute_s", "memory_s",
        "collective_s", "step_s", "useful_flops_ratio", "mfu")


def load(mesh: Optional[str] = "16x16", tag: Optional[str] = None) -> List[Dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(ART, "*.json"))):
        base = os.path.basename(path)[:-5]
        parts = base.split("__")
        meshtag = parts[2] if len(parts) > 2 else ""
        with open(path) as f:
            r = json.load(f)
        rmesh = r.get("mesh", "")
        rest = meshtag[len(rmesh):]
        file_tag = rest[1:] if rest.startswith("_") else None
        if tag != file_tag:
            continue
        if mesh and rmesh != mesh:
            continue
        rows.append(r)
    order = {"train_4k": 0, "prefill_32k": 1, "decode_32k": 2, "long_500k": 3}
    rows.sort(key=lambda r: (r["arch"], order.get(r["shape"], 9)))
    return rows


def csv_rows(rows: List[Dict]) -> List[str]:
    out = []
    for r in rows:
        vals = []
        for c in COLS:
            v = r.get(c, "")
            if isinstance(v, float):
                v = f"{v:.3e}" if "_s" in c else f"{v:.3f}"
            vals.append(str(v))
        out.append(",".join(vals))
    return out


def markdown_table(rows: List[Dict]) -> str:
    hdr = ("| arch | shape | bound | compute (s) | memory (s) | collective (s) "
           "| useful FLOPs | MFU |\n|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | **{r['bound']}** "
            f"| {r['compute_s']:.2e} | {r['memory_s']:.2e} "
            f"| {r['collective_s']:.2e} | {r['useful_flops_ratio']:.2f} "
            f"| {r['mfu']:.3f} |")
    return hdr + "\n".join(lines)


def main() -> None:
    print("roofline," + ",".join(COLS))
    for line in csv_rows(load("16x16")):
        print("roofline," + line)
    mp = load("2x16x16")
    if mp:
        print(f"# multi-pod cells compiled: {len(mp)}")


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
