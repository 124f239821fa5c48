"""Quantization-contract linter: the dtype-flow interpreter, the rule
set, the baseline mechanics, and the CLI grid.

The acceptance contract (ISSUE 8): the shipped sweep grid lints clean,
and a *fixture* program that re-introduces the PR 3 bug pattern — an
integer-dtype psum/accumulate of fractional bilinear votes — is caught
as a dtype-flow finding with jaxpr provenance. The fixtures here are
deliberately broken programs, never the shipped code.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core as jcore

from repro.analysis.dtype_flow import absval_from_aval, analyze_program
from repro.analysis.findings import (
    Finding,
    Provenance,
    load_baseline,
    split_by_baseline,
    write_baseline,
)
from repro.analysis.rules import audit_variant_space, default_rules
from repro.analysis import lint as lint_cli


def _contract(shape, dtype, lo, hi, integral=False):
    base = absval_from_aval(jcore.ShapedArray(shape, dtype))
    return base.with_(lo=float(lo), hi=float(hi), integral=integral, known=True)


SAT_INT16 = frozenset({(-32768.0, 32767.0)})


# ---------------------------------------------------------------------------
# the PR 3 bug class: fixtures must be caught, the sanctioned store must not
# ---------------------------------------------------------------------------


def test_pr3_fixture_int_psum_of_fractional_votes_is_caught():
    """The exact PR 3 pattern: bilinear (fractional) votes narrowed to an
    integer dtype before an integer psum inside a shard_map body."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("segments",))

    def fixture(votes):  # (S, E) fractional bilinear weights in [0, 1]
        def local(v):
            dsi = v.sum(axis=0)
            # BUG (on purpose): narrows fractional votes to int before psum
            return jax.lax.psum(dsi.astype(jnp.int32), "segments")

        return jax.shard_map(local, mesh=mesh, in_specs=(P("segments"),),
                             out_specs=P(), check_vma=False)(votes)

    ctx = analyze_program(
        fixture,
        (jax.ShapeDtypeStruct((1, 8), jnp.float32),),
        [_contract((1, 8), jnp.float32, 0.0, 1.0)],
        entry="fixture-pr3",
        rules=default_rules(),
        sanctioned_clips=SAT_INT16,
    )
    truncs = [f for f in ctx.findings if f.kind == "float-to-int-truncation"]
    assert truncs, "the PR 3 bug pattern must be a dtype-flow finding"
    f = truncs[0]
    # jaxpr provenance: primitive, source equation, enclosing call stack
    assert f.rule == "dtype-flow"
    assert f.provenance.primitive == "convert_element_type"
    assert "shard_map" in f.provenance.call_stack
    assert f.provenance.source and f.provenance.source != "<unknown>"
    assert "test_analysis" in f.provenance.source


def test_sanctioned_saturating_store_is_clean():
    """round + clamp-to-declared-format + cast is the Table 1 store, not a
    bug: clamp provenance sanctions the cast."""

    def store(votes):
        v = jnp.clip(jnp.round(votes), -32768, 32767)
        return v.astype(jnp.int16)

    ctx = analyze_program(
        store,
        (jax.ShapeDtypeStruct((8,), jnp.float32),),
        [_contract((8,), jnp.float32, 0.0, 1e6)],
        entry="store",
        rules=default_rules(),
        sanctioned_clips=SAT_INT16,
    )
    assert ctx.findings == []


def test_unclamped_fractional_cast_is_caught_even_in_range():
    """Interval containment is NOT sanction: a fractional value whose range
    happens to fit int16 still loses its fractional part."""

    def fixture(votes):
        return votes.astype(jnp.int16)  # bounds fit, fraction discarded

    ctx = analyze_program(
        fixture,
        (jax.ShapeDtypeStruct((8,), jnp.float32),),
        [_contract((8,), jnp.float32, 0.0, 0.75)],
        entry="fixture-inrange",
        rules=default_rules(),
        sanctioned_clips=SAT_INT16,
    )
    assert [f.kind for f in ctx.findings] == ["float-to-int-truncation"]


def test_clamp_to_undeclared_bounds_is_not_sanctioned():
    """A clamp only sanctions the cast if its bounds match a declared
    format — clip(x, 0, 100) before an int cast is still a truncation."""

    def fixture(votes):
        return jnp.clip(votes, 0.0, 100.0).astype(jnp.int16)

    ctx = analyze_program(
        fixture,
        (jax.ShapeDtypeStruct((8,), jnp.float32),),
        [_contract((8,), jnp.float32, 0.0, 1e6)],
        entry="fixture-undeclared-clip",
        rules=default_rules(),
        sanctioned_clips=SAT_INT16,
    )
    assert [f.kind for f in ctx.findings] == ["float-to-int-truncation"]


# ---------------------------------------------------------------------------
# overflow proofs
# ---------------------------------------------------------------------------


def test_int16_scan_accumulator_overflow_is_proven():
    """600 frames x up-to-64 votes/frame cannot fit int16: the scan
    closed-form linear-growth bound must prove the wrap statically."""

    def fixture(frames_votes):  # (600, 64) 0/1 vote mask
        def body(acc, v):
            votes = jnp.sum(v).astype(jnp.int16)
            return acc + votes, None

        return jax.lax.scan(body, jnp.zeros((), jnp.int16), frames_votes)[0]

    ctx = analyze_program(
        fixture,
        (jax.ShapeDtypeStruct((600, 64), jnp.float32),),
        [_contract((600, 64), jnp.float32, 0.0, 1.0, integral=True)],
        entry="fixture-overflow",
        rules=default_rules(),
        sanctioned_clips=SAT_INT16,
    )
    kinds = {f.kind for f in ctx.findings}
    assert "int-overflow" in kinds
    # 600 * 64 = 38400 > 32767, caught at the accumulating add
    prims = {f.provenance.primitive for f in ctx.findings if f.kind == "int-overflow"}
    assert "add" in prims or "scan" in prims


def test_int32_accumulator_headroom_is_proven_not_flagged():
    """The same accumulation into int32 is within range: no finding, and
    the proven bound is published as a fact."""

    def ok(frames_votes):
        def body(acc, v):
            votes = jnp.sum(v).astype(jnp.int32)
            return acc + votes, None

        return jax.lax.scan(body, jnp.zeros((), jnp.int32), frames_votes)[0]

    ctx = analyze_program(
        ok,
        (jax.ShapeDtypeStruct((600, 64), jnp.float32),),
        [_contract((600, 64), jnp.float32, 0.0, 1.0, integral=True)],
        entry="ok-int32",
        rules=default_rules(),
        sanctioned_clips=SAT_INT16,
    )
    assert [f for f in ctx.findings if f.kind == "int-overflow"] == []
    lo, hi = ctx.facts["int_bounds"]["int32"]
    assert hi >= 600 * 64  # the closed-form bound actually propagated
    assert hi < np.iinfo(np.int32).max


@pytest.mark.parametrize("quant", ["float", "quant"])
def test_chunked_vote_is_bounded_at_four_frames_of_events(quant):
    """The matmul sweep's nearest vote contracts 4 x E int8 one-hot rows
    a step: the int32 dot is bounded by k = 4 E, and the DSI carry by
    the proof capacity's votes (plus one step of the closed form's
    slack), far inside int32."""
    from repro.analysis.dtype_flow import Rule
    from repro.core.pipeline import VOTE_CHUNK_FRAMES

    class Record(Rule):
        rule_id = "record"

        def __init__(self):
            self.dots, self.carries = [], []

        def on_eqn(self, ctx, eqn, ins, outs):
            if eqn.primitive.name == "dot_general" and all(
                    np.dtype(a.dtype) == np.int8 for a in ins):
                self.dots.append(outs[0])
            elif eqn.primitive.name == "scan":
                self.carries += [o for o in outs if np.dtype(o.dtype) == np.int32]

    entry, = [e for e in lint_cli.build_entries("quick")
              if e["name"] == f"sweep[matmul,batched,nearest,{quant}]"]
    rec = Record()
    ctx = analyze_program(entry["fn"], entry["args"], entry["contracts"],
                          entry=entry["name"], rules=default_rules() + [rec],
                          sanctioned_clips=entry["policy"].sanctioned_clip_bounds())
    assert ctx.findings == []
    k = VOTE_CHUNK_FRAMES * lint_cli.TRACE_EVENTS
    assert [(np.dtype(d.dtype), d.lo, d.hi, d.known) for d in rec.dots] == [
        (np.dtype(np.int32), 0, k, True)]
    votes = lint_cli.PROOF_CAPACITY_FRAMES * lint_cli.TRACE_EVENTS
    assert rec.carries and all(
        c.known and votes <= c.hi <= votes + k for c in rec.carries)


def test_unknown_ranges_do_not_produce_noise_findings():
    """Unconstrained int inputs carry the dtype-default interval; adding
    two must NOT be reported — overflow findings are proofs only."""

    def f(a, b):
        return a + b

    s = jax.ShapeDtypeStruct((4,), jnp.int32)
    ctx = analyze_program(f, (s, s), None, entry="unknown", rules=default_rules())
    assert ctx.findings == []


# ---------------------------------------------------------------------------
# host-sync / f64 / weak_type
# ---------------------------------------------------------------------------


def test_host_sync_callback_is_caught():
    def fixture(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    ctx = analyze_program(
        fixture,
        (jax.ShapeDtypeStruct((4,), jnp.float32),),
        None,
        entry="fixture-hostsync",
        rules=default_rules(),
    )
    hs = [f for f in ctx.findings if f.rule == "host-sync"]
    assert len(hs) == 1
    assert hs[0].provenance.primitive == "debug_print"


def test_f64_promotion_is_caught():
    with jax.enable_x64():

        def fixture(x):
            return x.astype(jnp.float64) * 2.0

        ctx = analyze_program(
            fixture,
            (jax.ShapeDtypeStruct((4,), jnp.float32),),
            None,
            entry="fixture-f64",
            rules=default_rules(),
        )
    assert "f64-promotion" in {f.kind for f in ctx.findings}


def test_weak_type_output_is_warned():
    def fixture(x):
        return jnp.sum(x), 6.0  # unanchored python scalar output

    ctx = analyze_program(
        fixture,
        (jax.ShapeDtypeStruct((4,), jnp.float32),),
        None,
        entry="fixture-weak",
        rules=default_rules(),
    )
    weak = [f for f in ctx.findings if f.kind == "weak-type-leak"]
    assert weak and all(f.severity == "warning" for f in weak)


# ---------------------------------------------------------------------------
# recompilation audit
# ---------------------------------------------------------------------------


def test_variant_space_bound_holds_for_default_config():
    from repro.serving.emvs_stream import StreamConfig

    cfg = StreamConfig()
    findings, summary = audit_variant_space(cfg, 64)
    assert findings == []
    assert summary["variants"] <= summary["bound"]
    assert summary["s_buckets"] == tuple(cfg.segment_buckets)
    # capacities are the bucketed frame counts, deduped
    assert all(c % 4 == 0 for c in summary["capacities"])


def test_variant_space_shard_rounding_merges_buckets():
    from repro.serving.emvs_stream import StreamConfig
    from repro.serving.sweep_dispatcher import enumerate_variant_space

    cfg = StreamConfig(sweep="sharded")
    space = enumerate_variant_space(cfg, 16, mesh_segments=8)
    # (1, 2, 4) all round up to 8 on an 8-way mesh: one shard-stable bucket
    assert space["s_buckets"] == (8,)
    assert len(space["variants"]) == len(space["capacities"])
    findings, summary = audit_variant_space(cfg, 16, mesh_segments=8)
    assert findings == []
    assert summary["variants"] <= summary["bound"]


def test_unbounded_variant_space_is_a_finding():
    from repro.serving.emvs_stream import StreamConfig

    findings, _ = audit_variant_space(StreamConfig(), None)
    assert [f.kind for f in findings] == ["unbounded-variant-space"]
    assert findings[0].rule == "recompilation"


# ---------------------------------------------------------------------------
# baseline / suppression mechanics
# ---------------------------------------------------------------------------


def _dummy_finding(kind="float-to-int-truncation", line=10):
    return Finding(
        rule="dtype-flow",
        kind=kind,
        entry="sweep[matmul,batched,bilinear,quant]",
        message="m",
        provenance=Provenance(
            primitive="convert_element_type",
            source=f"repro/core/voting.py:{line} (vote_onehot_matmul)",
        ),
    )


def test_fingerprint_is_stable_across_line_churn():
    assert _dummy_finding(line=10).fingerprint == _dummy_finding(line=99).fingerprint


def test_baseline_roundtrip_and_suppression(tmp_path):
    f1 = _dummy_finding()
    f2 = _dummy_finding(kind="int-overflow")
    path = tmp_path / "baseline.json"
    write_baseline(str(path), [f1])
    baseline = load_baseline(str(path))
    new, suppressed = split_by_baseline([f1, f2], baseline)
    assert suppressed == [f1]
    assert new == [f2]


# ---------------------------------------------------------------------------
# the shipped grid: every sweep program lints clean (the CI gate's core)
# ---------------------------------------------------------------------------


def test_quick_grid_lints_clean(tmp_path):
    out = tmp_path / "findings.json"
    rc = lint_cli.main(
        ["--grid", "quick", "--baseline", "analysis_baseline.json", "--json", str(out)]
    )
    assert rc == 0
    data = json.loads(out.read_text())
    assert data["new"] == []
    assert data["report"]["entries"]  # something actually ran


@pytest.mark.slow
def test_full_grid_lints_clean_with_proofs():
    findings, report = lint_cli.run_lint("full")
    assert findings == [], [f.render() for f in findings]
    # every formulation x backend x voting x quantization combo traced
    assert len(report["entries"]) == 3 * 2 * 2 * 2 + 4
    # the int32 accumulator proof at the paper-scale capacity
    proofs = report["int_bound_proofs"]
    assert proofs["int32"]["headroom"] >= 0
    assert proofs["int16"]["headroom"] >= 0
    for summary in report["variant_space"].values():
        assert summary["variants"] <= summary["bound"]


def _higher_order_primitives(jaxpr, names: set) -> set:
    """Names of every primitive in `jaxpr` that carries a sub-jaxpr."""
    for eqn in jaxpr.eqns:
        subs = [p for v in eqn.params.values()
                for p in (v if isinstance(v, (tuple, list)) else (v,))
                if isinstance(p, (jcore.Jaxpr, jcore.ClosedJaxpr))]
        if subs:
            names.add(eqn.primitive.name)
        for sub in subs:
            _higher_order_primitives(getattr(sub, "jaxpr", sub), names)
    return names


def test_linter_handles_every_higher_order_primitive():
    """An unhandled primitive that carries a sub-jaxpr is skipped whole:
    the linter then loses clamp provenance inside it and reports false
    truncations (JAX renaming `pjit` to `jit` did exactly that). Every
    such primitive in the shipped grid, and in the JAX transformations
    the code base uses, must have a `_prim_<name>` handler."""
    from repro.analysis.dtype_flow import DtypeFlowAnalyzer

    @jax.custom_jvp
    def cj(x):
        return x * 2

    cj.defjvp(lambda p, t: (p[0] * 2, t[0] * 2))

    @jax.custom_vjp
    def cv(x):
        return x * 2

    cv.defvjp(lambda x: (x * 2, None), lambda _, g: (g * 2,))

    def fixture(x):
        y = jax.checkpoint(jnp.sin)(x) + cj(x) + cv(x)
        y = jax.lax.cond(y[0] > 0, lambda v: v + 1, lambda v: v - 1, y)
        y = jax.lax.while_loop(lambda v: v[0] < 3, lambda v: v + 1, y)
        y, _ = jax.lax.scan(lambda c, v: (c + v, None), y, jnp.ones((2, 4)))
        return jax.jit(jnp.cos)(y)

    names = _higher_order_primitives(
        jax.make_jaxpr(fixture)(jnp.zeros(4)).jaxpr, set())
    for entry in lint_cli.build_entries("full"):
        names = _higher_order_primitives(
            jax.make_jaxpr(entry["fn"])(*entry["args"]).jaxpr, names)
    assert {"jit", "pallas_call", "shard_map", "scan"} <= names
    missing = sorted(n for n in names if not hasattr(
        DtypeFlowAnalyzer, "_prim_" + n.replace("-", "_")))
    assert missing == [], f"no linter handler for {missing}"


def test_broken_policy_would_be_caught_end_to_end():
    """End-to-end negative control for the gate: linting a quantized sweep
    with the sanctioned clamp set emptied must surface the int16 store as
    a truncation finding — proving the grid test can actually fail."""
    entry = next(
        e
        for e in lint_cli.build_entries("quick")
        if e["name"] == "sweep[matmul,batched,bilinear,quant]"
    )

    class NoSanction:
        @staticmethod
        def sanctioned_clip_bounds():
            return frozenset()

    entry["policy"] = NoSanction()
    findings, _ = lint_cli.lint_entry(entry)
    assert "float-to-int-truncation" in {f.kind for f in findings}


# ---------------------------------------------------------------------------
# S2: boundary-inclusive saturation monitor
# ---------------------------------------------------------------------------


def test_store_saturation_fraction_sees_clipped_volumes():
    from repro.core import dsi as dsi_lib

    info = np.iinfo(np.int16)
    hot = jnp.full((4, 4), 10 * info.max, jnp.int32)
    stored = dsi_lib.storage_roundtrip(hot)
    # the strict pre-store probe is blind after the clip...
    assert float(dsi_lib.saturation_fraction(stored)) == 0.0
    # ...the boundary-inclusive streaming monitor is not
    assert float(dsi_lib.store_saturation_fraction(stored)) == 1.0
    cold = jnp.zeros((4, 4), jnp.int32)
    assert float(dsi_lib.store_saturation_fraction(cold)) == 0.0
