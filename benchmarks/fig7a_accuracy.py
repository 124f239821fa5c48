"""Paper Fig 7a: AbsRel per sequence — original EMVS vs our reformulated
framework (rescheduled + nearest voting + Table-1 quantization).

Claim reproduced: sims favour the original slightly (max diff < 1.78%);
slider sequences can even favour the reformulated framework.
"""
from __future__ import annotations

from benchmarks._emvs_common import SEQUENCES, absrel_for
from repro.core.pipeline import EMVSOptions

ORIGINAL = EMVSOptions(voting="bilinear", quantized=False,
                       formulation="scatter")
REFORMULATED = EMVSOptions(voting="nearest", quantized=True,
                           formulation="matmul")


def run() -> dict:
    rows = {}
    worst = 0.0
    for seq in SEQUENCES:
        e_o = absrel_for(seq, ORIGINAL)
        e_r = absrel_for(seq, REFORMULATED)
        rows[seq] = {"original_emvs": e_o, "reformulated": e_r,
                     "diff": e_r - e_o}
        worst = max(worst, e_r - e_o)
    return {"rows": rows, "max_regression": worst,
            "paper_claim_max_diff": 0.0178,
            "claim_ok": bool(worst < 0.05)}


def main() -> None:
    out = run()
    print("== Fig 7a: original EMVS vs reformulated (AbsRel) ==")
    print(f"{'sequence':22s} {'original':>9s} {'reformed':>9s} {'diff':>8s}")
    for seq, r in out["rows"].items():
        print(f"{seq:22s} {r['original_emvs']:9.4f} {r['reformulated']:9.4f} "
              f"{r['diff']:+8.4f}")
    print(f"max regression {out['max_regression']:+.4f} "
          f"(paper: <{out['paper_claim_max_diff']:.4f}; "
          f"{'OK' if out['claim_ok'] else 'VIOLATED'})")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
