"""Roofline report: the EMVS kernel-fusion ladder (analytic, always
available) plus the LM dry-run sweep table when its artifacts exist.

The fusion section gates the tentpole claim of the fused Pallas sweep:
each fusion stage (unfused -> fused int16 store -> fused detection) must
sit STRICTLY closer to the roofline bound than the previous one — fusion
only deletes HBM traffic, so a rung that fails the gate means the model
(or the kernel) has regrown a round-trip.

    PYTHONPATH=src python -m benchmarks.roofline_report [--dry-run]

`--dry-run` additionally writes the ladder into the namespaced
`"dry_run"` section of BENCH_emvs.json (never the top level, so the CI
smoke cannot poison tracked full-run records).

The LM table needs the dry-run sweep artifacts first:

    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
from __future__ import annotations

import argparse
import os

from repro.launch.roofline import emvs_fusion_ladder

DEFAULT_DIR = "results/dryrun"

# Eventor operating point: DAVIS240 sensor, paper's 64-plane sweep
FUSION_SHAPE = dict(nz=64, h=180, w=240, events=1024, frames=8)


def fusion_report(shape: dict | None = None) -> dict:
    """Compute the ladder and enforce the strictly-closer gate."""
    shape = dict(shape or FUSION_SHAPE)
    ladder = emvs_fusion_ladder(**shape)
    violations = []
    for prev, cur in zip(ladder, ladder[1:]):
        if not cur.bound_gap < prev.bound_gap:
            violations.append(
                f"{cur.name} (gap {cur.bound_gap:.3f}) is not strictly "
                f"closer to the roofline bound than {prev.name} "
                f"(gap {prev.bound_gap:.3f})")
    return {
        "shape": shape,
        "stages": [r.to_json() for r in ladder],
        "violations": violations,
        "fused_vs_unfused_bytes_ratio": (
            ladder[-1].hbm_bytes / ladder[0].hbm_bytes),
    }


def _print_fusion(rep: dict) -> None:
    print("== EMVS sweep fusion ladder (analytic two-term roofline) ==")
    s = rep["shape"]
    print(f"shape: nz={s['nz']} h={s['h']} w={s['w']} events={s['events']} "
          f"frames={s['frames']} quantized={s.get('quantized', True)}")
    print(f"{'stage':<14} {'HBM MiB':>9} {'intensity':>10} "
          f"{'memory us':>10} {'compute us':>11} {'bound gap':>10}")
    for st in rep["stages"]:
        print(f"{st['name']:<14} {st['hbm_bytes'] / 2**20:>9.2f} "
              f"{st['intensity']:>10.1f} {st['memory_s'] * 1e6:>10.2f} "
              f"{st['compute_s'] * 1e6:>11.2f} {st['bound_gap']:>10.2f}")
    ratio = rep["fused_vs_unfused_bytes_ratio"]
    print(f"fused kernel moves {ratio:.2%} of the unfused HBM traffic")
    for v in rep["violations"]:
        print(f"VIOLATION: {v}")


def run(out_dir: str = DEFAULT_DIR) -> dict:
    if not os.path.isdir(out_dir):
        return {"error": f"no dry-run results in {out_dir}; run the sweep first",
                "rows": []}
    from benchmarks.summarize_dryrun import load

    recs = load(out_dir)
    compiled = [r for r in recs if "skipped" not in r]
    doms = {}
    for r in compiled:
        doms[r["roofline"]["dominant"]] = doms.get(r["roofline"]["dominant"], 0) + 1
    return {"rows": recs, "n": len(recs), "n_compiled": len(compiled),
            "dominant_histogram": doms}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true",
                    help="record the fusion ladder into the dry_run "
                         "namespace of BENCH_emvs.json (CI smoke)")
    ap.add_argument("--out-dir", default=DEFAULT_DIR,
                    help="LM dry-run artifact directory")
    args = ap.parse_args(argv)

    rep = fusion_report()
    _print_fusion(rep)

    if args.dry_run:
        try:
            from _emvs_common import update_bench_json
        except ImportError:
            from benchmarks._emvs_common import update_bench_json
        path = update_bench_json("roofline_report", {
            "dry_run": True,
            "fusion": rep,
        })
        print(f"\nwrote dry_run/roofline_report -> {path}")

    out = run(args.out_dir)
    if "error" in out:
        print(f"\n{out['error']}")
    else:
        from benchmarks.summarize_dryrun import HEADER, fmt_row

        print("\n== Roofline (from the 512-device dry-run artifacts) ==")
        print(HEADER)
        for r in out["rows"]:
            print(fmt_row(r))
        print(f"\n{out['n']} cells ({out['n_compiled']} compiled); dominant-"
              f"term histogram: {out['dominant_histogram']}")

    if rep["violations"]:
        return 1
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
