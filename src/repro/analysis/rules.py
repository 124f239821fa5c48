"""The typed lint rules driven by the dtype-flow interpreter.

Three rule families, matching docs/quantization_contracts.md:

- :class:`DtypeFlowRule` — quantization/overflow contracts over value
  flow: fractional float->int casts without a sanctioned clamp (the
  PR 3 bilinear-truncation class), proven integer overflow, f64
  promotions, weak_type leaks.
- :class:`HostSyncRule` — host round-trip primitives (callbacks,
  infeed/outfeed) inside streaming-dispatched programs, enforcing the
  "no per-chunk device round-trips" docstring contract.
- :func:`audit_variant_space` — the recompilation audit: enumerates the
  dispatcher's compiled-variant space from ``StreamConfig`` buckets and
  verifies the |S buckets| x |capacities| bound and its coverage.
"""
from __future__ import annotations

import math
from typing import Any

import numpy as np

from repro.analysis.dtype_flow import AbsVal, Context, Rule, int_range
from repro.analysis.findings import Finding, Provenance

_INT_MAX_TRACKED = ("int8", "int16", "int32", "int64")


def _is_int(dtype: Any) -> bool:
    return np.issubdtype(np.dtype(dtype), np.integer)


def _is_float(dtype: Any) -> bool:
    return np.issubdtype(np.dtype(dtype), np.floating)


class DtypeFlowRule(Rule):
    """Quantization-contract checks on dtype and value flow."""

    rule_id = "dtype-flow"

    def on_eqn(self, ctx: Context, eqn: Any, ins: list[AbsVal], outs: list[AbsVal]) -> None:
        name = eqn.primitive.name
        if name == "convert_element_type":
            self._check_convert(ctx, eqn, ins[0], outs[0])
        else:
            self._check_int_growth(ctx, eqn, outs)
        for out in outs:
            if np.dtype(out.dtype) in (np.dtype(np.float64), np.dtype(np.complex128)):
                ctx.report(
                    eqn,
                    self.rule_id,
                    "f64-promotion",
                    f"{name} produces {np.dtype(out.dtype).name}; the datapaths "
                    "are f32/int — an f64 promotion doubles bandwidth and "
                    "breaks the fixed-point contracts",
                )
                break
        self._track_int_bounds(ctx, outs)

    def _check_convert(self, ctx: Context, eqn: Any, a: AbsVal, out: AbsVal) -> None:
        src = np.dtype(a.dtype)
        dst = np.dtype(out.dtype)
        if not (_is_float(src) and _is_int(dst)):
            return
        # (1) fractional truncation: the PR 3 bug class.  A float->int
        # cast of a possibly-fractional value is only sanctioned when the
        # operand was just clamped to a range some quant policy declares
        # (clamp provenance), i.e. it is the Table 1 saturating store.
        if not a.integral and a.clip not in ctx.sanctioned_clips:
            ctx.report(
                eqn,
                self.rule_id,
                "float-to-int-truncation",
                f"cast {src.name}->{dst.name} of a possibly-fractional value "
                f"(bounds [{a.lo}, {a.hi}], clamp={a.clip}) discards the "
                "fractional part; either round-and-clamp to a declared "
                "fixed-point format first, or keep the accumulator float "
                "(bilinear votes carry fractional weights — see PR 3)",
            )
        # (2) proven wrap: the *mathematical* interval of the operand
        # exceeds the target integer range.  Only claimed when the
        # interval was actually propagated (known) and finite — dtype
        # defaults for unconstrained inputs are not proofs.
        rlo, rhi = int_range(dst)
        if (
            a.known
            and math.isfinite(a.lo)
            and math.isfinite(a.hi)
            and (math.floor(a.lo) < rlo or math.ceil(a.hi) > rhi)
            and a.clip not in ctx.sanctioned_clips
        ):
            ctx.report(
                eqn,
                self.rule_id,
                "int-overflow",
                f"cast to {dst.name} can wrap: worst-case value in "
                f"[{a.lo}, {a.hi}] exceeds [{rlo:.0f}, {rhi:.0f}]; clamp to a "
                "declared format before the cast (saturating store)",
            )

    def _check_int_growth(self, ctx: Context, eqn: Any, outs: list[AbsVal]) -> None:
        # integer arithmetic whose propagated worst case exceeds the dtype
        # range — the accumulate-side wrap (e.g. int16 += votes)
        for out in outs:
            dtype = np.dtype(out.dtype)
            if not _is_int(dtype):
                continue
            if not (out.known and math.isfinite(out.lo) and math.isfinite(out.hi)):
                continue
            rlo, rhi = int_range(dtype)
            if out.lo < rlo or out.hi > rhi:
                ctx.report(
                    eqn,
                    self.rule_id,
                    "int-overflow",
                    f"{eqn.primitive.name} on {dtype.name} can wrap: worst-case "
                    f"value in [{out.lo}, {out.hi}] exceeds [{rlo:.0f}, {rhi:.0f}] "
                    "(accumulate in a wider dtype, or lower the segment capacity)",
                )
                return

    def _track_int_bounds(self, ctx: Context, outs: list[AbsVal]) -> None:
        # publish the proven worst-case [lo, hi] per integer dtype so the
        # CLI can print "int32 accumulator bounded within range" proofs
        bounds = ctx.facts.setdefault("int_bounds", {})
        for out in outs:
            dtype = np.dtype(out.dtype)
            if not _is_int(dtype) or dtype.name not in _INT_MAX_TRACKED:
                continue
            if not (out.known and math.isfinite(out.lo) and math.isfinite(out.hi)):
                continue
            lo, hi = bounds.get(dtype.name, (0.0, 0.0))
            bounds[dtype.name] = (min(lo, out.lo), max(hi, out.hi))

    def on_outputs(self, ctx: Context, outs: list[AbsVal]) -> None:
        for i, out in enumerate(outs):
            if out.weak_type:
                ctx.findings.append(
                    Finding(
                        rule=self.rule_id,
                        kind="weak-type-leak",
                        entry=ctx.entry,
                        message=(
                            f"program output {i} has weak_type=True; weakly-typed "
                            "outputs re-promote downstream consumers and change "
                            "dtypes silently — anchor with an explicit astype"
                        ),
                        provenance=Provenance(primitive="<output>", source="<jaxpr outputs>"),
                        severity="warning",
                    )
                )


# primitives that force a host round-trip / sync inside a traced program
HOST_SYNC_PRIMITIVES = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "debug_print",
        "infeed",
        "outfeed",
    }
)


class HostSyncRule(Rule):
    """No host round-trips inside streaming-dispatched sweep programs."""

    rule_id = "host-sync"

    def on_eqn(self, ctx: Context, eqn: Any, ins: list[AbsVal], outs: list[AbsVal]) -> None:
        if eqn.primitive.name in HOST_SYNC_PRIMITIVES:
            ctx.report(
                eqn,
                self.rule_id,
                "host-round-trip",
                f"{eqn.primitive.name} forces a host sync inside a "
                "streaming-dispatched program; the dispatch layer relies on "
                "sweeps being enqueued asynchronously (no per-chunk device "
                "round-trips) — move host I/O outside the traced sweep",
            )


def default_rules() -> list[Rule]:
    return [DtypeFlowRule(), HostSyncRule()]


def audit_variant_space(
    stream_cfg: Any,
    max_segment_frames: int | None,
    *,
    mesh_segments: int = 1,
    entry: str = "variant-space",
) -> tuple[list[Finding], dict[str, Any]]:
    """Recompilation audit over the dispatcher's compiled-variant space.

    Statically enumerates every (S bucket, frame capacity) entry shape the
    dispatcher can stage for ``stream_cfg`` (via
    :func:`repro.serving.sweep_dispatcher.enumerate_variant_space`) and
    checks:

    - the space is bounded at all (``max_segment_frames`` declared);
    - |variants| == |S buckets| x |capacities| and the shard-rounded S
      buckets never exceed the configured bucket count — the jit-cache
      bound the streaming docs promise;
    - coverage: every dispatchable group size and frame count maps into
      an enumerated variant (no cache-key fragmentation at runtime);
    - planner usage: the actual ``DispatchPlanner`` is driven over
      exhaustive single-capacity and mixed loads (every frame count up
      to ``max_segment_frames``, every queue depth up to one past the
      top S bucket, both fairness policies) and every group it emits
      must land on an enumerated (S bucket, capacity) variant — the
      planner can never be the component that fragments the jit cache.
    """
    from repro.core.pipeline import DispatchPlanner, bucket_capacity
    from repro.serving.sweep_dispatcher import enumerate_variant_space

    findings: list[Finding] = []

    def report(kind: str, message: str) -> None:
        findings.append(
            Finding(
                rule="recompilation",
                kind=kind,
                entry=entry,
                message=message,
                provenance=Provenance(
                    primitive="<StreamConfig>",
                    source=f"segment_buckets={tuple(stream_cfg.segment_buckets)} "
                    f"sweep={stream_cfg.sweep} mesh_segments={mesh_segments}",
                ),
            )
        )

    if not max_segment_frames or max_segment_frames <= 0:
        report(
            "unbounded-variant-space",
            "no max_segment_frames declared: the capacity axis of the "
            "compiled-variant space is unbounded, so a long-running service "
            "can grow the jit cache without limit",
        )
        return findings, {
            "s_buckets": (),
            "capacities": (),
            "variants": 0,
            "bound": None,
        }

    space = enumerate_variant_space(
        stream_cfg, max_segment_frames, mesh_segments=mesh_segments
    )
    s_buckets = space["s_buckets"]
    capacities = space["capacities"]
    variants = space["variants"]
    bound = len(stream_cfg.segment_buckets) * len(capacities)

    if len(variants) != len(s_buckets) * len(capacities):
        report(
            "variant-bound-violated",
            f"enumerated {len(variants)} variants but |S buckets| x "
            f"|capacities| = {len(s_buckets) * len(capacities)}",
        )
    if len(s_buckets) > len(stream_cfg.segment_buckets):
        report(
            "variant-bound-violated",
            f"shard rounding produced {len(s_buckets)} S buckets from "
            f"{len(stream_cfg.segment_buckets)} configured — rounding must "
            "only merge buckets, never split them",
        )

    # coverage: the dispatcher's bucket lookup and capacity padding must
    # land inside the enumerated space for every feasible input
    top = max(s_buckets)
    for n in range(1, top + 1):
        b = next((x for x in s_buckets if x >= n), None)
        if b is None or b not in s_buckets:
            report(
                "variant-coverage-gap",
                f"group of {n} segments does not map to an enumerated S bucket",
            )
            break
    for f in range(1, max_segment_frames + 1):
        if bucket_capacity(f) not in capacities:
            report(
                "variant-coverage-gap",
                f"{f} frames pads to capacity {bucket_capacity(f)}, which is "
                "not in the enumerated capacity set",
            )
            break

    # planner usage: drive the real DispatchPlanner (the partition the
    # dispatcher stages) over exhaustive loads; every emitted group's
    # (padded S bucket, capacity) must be an enumerated variant
    planner = DispatchPlanner(tuple(s_buckets))
    variant_set = {(s, c) for s in s_buckets for c in capacities}
    groups_checked = 0

    def check_groups(groups) -> bool:
        nonlocal groups_checked
        for group, cap in groups:
            groups_checked += 1
            b = next((x for x in s_buckets if x >= len(group)), None)
            if b is None or (b, cap) not in variant_set:
                report(
                    "variant-coverage-gap",
                    f"planner emitted a group of {len(group)} segments at "
                    f"capacity {cap} -> variant ({b}, {cap}) outside the "
                    f"enumerated space",
                )
                return False
        return True

    ok = True
    for f in range(1, max_segment_frames + 1):
        for n in range(1, top + 2):  # one past the top bucket: must split
            segs = [(k * f, (k + 1) * f) for k in range(n)]
            ok = ok and check_groups(planner.plan(segs))
        if not ok:
            break
    if ok:
        # mixed load: every frame count in one queue (capacity changes
        # seal groups), fanned over two sessions under both fairness
        # policies through the tagged planner the multi-stream engine uses
        segs, frame = [], 0
        for f in range(1, max_segment_frames + 1):
            segs.append((frame, frame + f))
            frame += f
        items = [(k % 2, seg) for k, seg in enumerate(segs)]
        for fairness in ("fifo", "round_robin"):
            check_groups(planner.plan_tagged(items, fairness=fairness))

    summary = {
        "s_buckets": tuple(s_buckets),
        "capacities": tuple(capacities),
        "variants": len(variants),
        "bound": bound,
        "planner_groups_checked": groups_checked,
    }
    return findings, summary
