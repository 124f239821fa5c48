"""Paper Fig 4a: depth-estimation AbsRel, Bilinear vs Nearest voting.

Claim reproduced: "The maximum AbsRel difference between Nearest Voting
and original Bilinear Voting is about 1.18%."
"""
from __future__ import annotations

from benchmarks._emvs_common import SEQUENCES, absrel_for
from repro.core.pipeline import EMVSOptions


def run() -> dict:
    rows = {}
    worst_gap = 0.0
    for seq in SEQUENCES:
        e_bil = absrel_for(seq, EMVSOptions(voting="bilinear"))
        e_nea = absrel_for(seq, EMVSOptions(voting="nearest"))
        gap = abs(e_nea - e_bil)
        worst_gap = max(worst_gap, gap)
        rows[seq] = {"bilinear": e_bil, "nearest": e_nea, "gap": gap}
    return {"rows": rows, "max_gap": worst_gap,
            "paper_claim_max_gap": 0.0118,
            "claim_ok": bool(worst_gap < 0.025)}


def main() -> None:
    out = run()
    print("== Fig 4a: nearest vs bilinear voting (AbsRel) ==")
    print(f"{'sequence':22s} {'bilinear':>9s} {'nearest':>9s} {'gap':>8s}")
    for seq, r in out["rows"].items():
        print(f"{seq:22s} {r['bilinear']:9.4f} {r['nearest']:9.4f} "
              f"{r['gap']:8.4f}")
    print(f"max gap {out['max_gap']:.4f} "
          f"(paper: ~{out['paper_claim_max_gap']:.4f}; "
          f"{'OK' if out['claim_ok'] else 'VIOLATED'})")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
