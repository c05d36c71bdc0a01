"""Roofline analysis of dry-run records (deliverable (g)).

:func:`main` reads results/dryrun.json (written by repro.launch.dryrun)
and derives the three roofline terms per (arch x shape x mesh):

  compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
  memory     = HLO_bytes_per_device / HBM_bw
  collective = collective_bytes_per_device / ICI_bw

cost_analysis() of the partitioned module is per-device, so no further
/chips is needed.  HLO_FLOPs/bytes use the loop-free cost probes (XLA
counts loop bodies once; see launch/dryrun.probe_costs).  Hardware: TPU
v5e-like — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI (we charge
the conservative single-link figure; a v5e 2D torus has more).
"""
from __future__ import annotations

import json
import sys

PEAK_FLOPS = 197e12     # bf16 / chip
HBM_BW = 819e9          # bytes/s
ICI_BW = 50e9           # bytes/s per link (conservative single-link)


def analyze(rec: dict) -> dict:
    n = rec["n_devices"]
    t_compute = rec["probe_flops"] / PEAK_FLOPS
    t_memory = rec["probe_bytes"] / HBM_BW
    t_coll = rec["probe_collective_bytes"] / ICI_BW
    terms = dict(compute=t_compute, memory=t_memory, collective=t_coll)
    dominant = max(terms, key=terms.get)
    t_ideal = rec["model_flops"] / (n * PEAK_FLOPS)
    t_bound = max(terms.values())
    frac = t_ideal / t_bound if t_bound > 0 else float("nan")
    useful = rec["model_flops"] / max(rec["probe_flops"] * n, 1.0)
    return dict(arch=rec["arch"], shape=rec["shape"], mesh=rec["mesh"],
                t_compute=t_compute, t_memory=t_memory, t_collective=t_coll,
                dominant=dominant, t_ideal=t_ideal,
                roofline_fraction=frac, useful_flops_ratio=useful,
                peak_gib=rec["peak_bytes_per_dev"] / 2**30)


def main(path: str = "results/dryrun.json", mesh: str = "16x16"):
    recs = [r for r in json.load(open(path))
            if r.get("status") == "ok" and r["mesh"] == mesh]
    rows = [analyze(r) for r in recs]
    rows.sort(key=lambda r: r["roofline_fraction"])
    hdr = ("arch", "shape", "compute_s", "memory_s", "collective_s",
           "dominant", "ideal_s", "roofline_frac", "useful_ratio", "GiB/dev")
    print("| " + " | ".join(hdr) + " |")
    print("|" + "---|" * len(hdr))
    for r in rows:
        print(f"| {r['arch']} | {r['shape']} | {r['t_compute']:.2e} | "
              f"{r['t_memory']:.2e} | {r['t_collective']:.2e} | "
              f"{r['dominant']} | {r['t_ideal']:.2e} | "
              f"{r['roofline_fraction']:.3f} | "
              f"{r['useful_flops_ratio']:.3f} | {r['peak_gib']:.1f} |")
    print()
    worst = rows[0] if rows else None
    coll_bound = [r for r in rows if r["dominant"] == "collective"]
    if worst:
        print(f"worst roofline fraction: {worst['arch']} x {worst['shape']}"
              f" ({worst['roofline_fraction']:.3f}, {worst['dominant']}-bound)")
    if coll_bound:
        c = min(coll_bound, key=lambda r: r["roofline_fraction"])
        print(f"most collective-bound: {c['arch']} x {c['shape']}"
              f" ({c['t_collective']:.2e}s collective)")
    return rows


if __name__ == "__main__":
    main(*sys.argv[1:])
