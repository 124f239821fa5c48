"""Benchmark driver: one section per paper table/figure + the roofline.

    PYTHONPATH=src python -m benchmarks.run [--skip-slow]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-slow", action="store_true")
    args = ap.parse_args()

    from benchmarks import (
        fig4a_voting,
        fig4b_quant,
        fig7a_accuracy,
        memory_footprint,
        roofline_report,
        segment_batching,
        table3_runtime,
    )

    sections = [
        ("Table 3 (runtime per event frame)", table3_runtime.main),
        ("Segment batching (looped vs batched sweep)", segment_batching.main),
        ("Fig 4a (nearest vs bilinear voting)", fig4a_voting.main),
        ("Fig 4b (hybrid quantization)", fig4b_quant.main),
        ("Fig 7a (original vs reformulated)", fig7a_accuracy.main),
        ("§2.3 (memory footprint)", memory_footprint.main),
        ("Roofline (dry-run artifacts)", roofline_report.main),
    ]
    failures = 0
    for title, fn in sections:
        print("\n" + "=" * 72)
        print(f"### {title}")
        print("=" * 72)
        t0 = time.time()
        try:
            fn()
        except Exception:
            failures += 1
            traceback.print_exc()
        print(f"[{time.time() - t0:.1f}s]")
    print("\n" + ("ALL BENCHMARKS OK" if failures == 0
                  else f"{failures} BENCHMARK SECTIONS FAILED"))
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
