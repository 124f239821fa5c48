"""Paper Fig 4b: AbsRel with vs without Table-1 hybrid quantization.

Claim reproduced: "The maximum AbsRel difference before and after
quantization is about 1.01%."
"""
from __future__ import annotations

from benchmarks._emvs_common import SEQUENCES, absrel_for
from repro.core.pipeline import EMVSOptions


def run() -> dict:
    rows = {}
    worst_gap = 0.0
    for seq in SEQUENCES:
        e_f = absrel_for(seq, EMVSOptions(quantized=False))
        e_q = absrel_for(seq, EMVSOptions(quantized=True))
        gap = abs(e_q - e_f)
        worst_gap = max(worst_gap, gap)
        rows[seq] = {"float32": e_f, "table1_quantized": e_q, "gap": gap}
    return {"rows": rows, "max_gap": worst_gap,
            "paper_claim_max_gap": 0.0101,
            "claim_ok": bool(worst_gap < 0.04)}


def main() -> None:
    out = run()
    print("== Fig 4b: Table-1 quantization impact (AbsRel) ==")
    print(f"{'sequence':22s} {'float32':>9s} {'quant':>9s} {'gap':>8s}")
    for seq, r in out["rows"].items():
        print(f"{seq:22s} {r['float32']:9.4f} {r['table1_quantized']:9.4f} "
              f"{r['gap']:8.4f}")
    print(f"max gap {out['max_gap']:.4f} "
          f"(paper: ~{out['paper_claim_max_gap']:.4f}; "
          f"{'OK' if out['claim_ok'] else 'VIOLATED'})")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
