"""Record cost_table.json entries for the fused-kernel sweep backend.

The DispatchPlanner can only price a variant it has samples for; the
table's pre-existing rows cover the matmul formulation ("batched"), so
without this recorder a `formulation="kernel"` stream would fall back to
the planner's uncalibrated prior. This measures warm wall times of
`sweep_segment_batch` with `formulation="kernel"` at the same
(s_bucket, capacity) grid points as the existing matmul rows and merges
them under the `batched+kernel` backend axis (`cost_table.backend_name`).

    PYTHONPATH=src python -m benchmarks.record_kernel_costs [--dry-run]

On CPU the kernel runs under the Pallas interpreter, so the recorded
costs price exactly what a CPU stream would dispatch; on TPU/GPU the
same command records the compiled kernel. The mode is chosen here and
passed explicitly (`kernel_interpret`), so a platform that should
compile the kernel but cannot fails instead of interpreting.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.camera import CameraModel
from repro.core.dsi import DSIConfig
from repro.core.pipeline import EMVSOptions, SegmentBatch, sweep_segment_batch
from repro.kernels.platform import compiled_kernels_supported
from repro.profiling.cost_table import CostTable, VariantKey, backend_name

# the (s_bucket, capacity) points the matmul rows already cover
GRID = ((1, 4), (1, 8), (1, 12), (2, 8), (2, 12), (4, 8), (4, 12))


def _synthetic_batch(s: int, c: int, e: int, cam: CameraModel,
                     seed: int = 0) -> SegmentBatch:
    rng = np.random.default_rng(seed)
    xy = rng.uniform((0, 0), (cam.width - 1, cam.height - 1),
                     (s, c, e, 2)).astype(np.float32)
    R = np.broadcast_to(np.eye(3, dtype=np.float32), (s, c, 3, 3)).copy()
    t = np.zeros((s, c, 3), np.float32)
    t[..., 0] = np.linspace(0.0, 0.05 * c, c, dtype=np.float32)
    return SegmentBatch(
        xy=jnp.asarray(xy),
        valid=jnp.ones((s, c, e), jnp.float32),
        frame_valid=jnp.ones((s, c), jnp.float32),
        poses_R=jnp.asarray(R),
        poses_t=jnp.asarray(t),
        ref_R=jnp.asarray(R[:, 0]),
        ref_t=jnp.asarray(t[:, 0]),
    )


def record(table: CostTable, *, events: int, repeats: int,
           quantized_points: tuple[tuple[int, int], ...],
           grid: tuple[tuple[int, int], ...] = GRID) -> list[str]:
    cam = CameraModel()
    dsi_cfg = DSIConfig.for_camera(cam, num_planes=32)
    backend = backend_name("batched", "kernel")
    interpret = not compiled_kernels_supported()
    print(f"kernel mode: {'interpreted' if interpret else 'compiled'}",
          flush=True)
    rows = []
    jobs = [(s, c, False) for s, c in grid]
    jobs += [(s, c, True) for s, c in quantized_points]
    for s, c, quantized in jobs:
        opts = EMVSOptions(voting="nearest", formulation="kernel",
                           quantized=quantized, kernel_interpret=interpret)
        batch = _synthetic_batch(s, c, events, cam)
        key = VariantKey(s_bucket=s, capacity=c, backend=backend,
                         interpolation="nearest", quantized=quantized)

        def run_once():
            out = sweep_segment_batch(cam, dsi_cfg, batch, opts)
            jax.tree.map(
                lambda a: a.block_until_ready() if hasattr(
                    a, "block_until_ready") else a, out)

        run_once()  # cold compile — never recorded
        for _ in range(repeats):
            t0 = time.perf_counter()
            run_once()
            table.record(key, time.perf_counter() - t0)
        stats = table.entry_stats(key)
        rows.append(f"{key.to_str()}: mean {stats['mean_s']:.4f}s "
                    f"over {stats['count']} warm run(s)")
        print(rows[-1], flush=True)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="one tiny grid point, few events (CI smoke); "
                         "does NOT write the table")
    ap.add_argument("--table", default="cost_table.json")
    ap.add_argument("--events", type=int, default=1024,
                    help="events per aggregated frame")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()

    table = CostTable()
    if args.dry_run:
        record(table, events=64, repeats=1, grid=((1, 4),),
               quantized_points=((1, 4),))
        print("dry run: table not written")
        return
    record(table, events=args.events, repeats=args.repeats,
           quantized_points=((1, 4), (1, 8), (1, 12)))
    try:
        merged = CostTable.load(args.table)
    except FileNotFoundError:
        merged = CostTable()
    merged.merge(table)
    merged.save(args.table)
    print(f"merged {len(table)} kernel-backend variant(s) into {args.table} "
          f"({len(merged)} total)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
