"""Cost-aware dispatch planning: planner, cost model, replayer, SLO.

What this file pins (docs/dispatch_planning.md):

  * `DispatchPlanner` partitions are bitwise-equal to the PR 5/6
    module-level planners for ANY cost model — a cost model may change
    WHEN the scheduler dispatches, never WHICH groups form;
  * partition validity and per-session FIFO hold under any cost model
    (hypothesis, random affine models included);
  * the cost table round-trips through its schema-versioned JSON and
    rejects malformed payloads with typed errors;
  * the affine fit recovers exact affine data and the table model
    prefers measured means over the fallback;
  * the replayer reproduces scheduling decisions deterministically, and
    SLO monotonicity holds on burst traces: tightening
    `target_latency_s` never increases the replayed predicted p99
    (burst-scoped deliberately — under sustained overload an eagerly
    split schedule can pay more total overhead, so the general-trace
    claim is false; the CI gate replays the burst profile);
  * live engines: a null cost model (or no deadline) leaves the
    adaptive schedule bitwise-identical to the pre-SLO engine, a real
    model + deadline keeps results bitwise-equal to offline while the
    SLO counters show deadline-driven decisions, and the opt-in
    profiler records a coherent trace + warm cost samples.
"""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.dsi import DSIConfig
from repro.core.pipeline import (
    DispatchPlanner,
    EMVSOptions,
    bucket_capacity,
    plan_dispatch_groups,
    plan_dispatch_groups_tagged,
    run_emvs,
)
from repro.events.aggregation import aggregate
from repro.events.simulator import EventStream
from repro.profiling import (
    AffineCostModel,
    CostTable,
    CostTableError,
    NullCostModel,
    SweepProfiler,
    TableCostModel,
    VariantKey,
    fit_affine_model,
)
from repro.profiling.calibrate import main as calibrate_main
from repro.profiling.cost_model import model_from_table
from repro.serving.dispatch_replay import (
    Arrival,
    ReplayConfig,
    check_slo_burst,
    percentile,
    planner_for,
    replay_schedule,
)
from repro.serving.emvs_stream import (
    EMVSStreamEngine,
    StreamConfig,
    iter_event_chunks,
)
from test_segment_batching import _assert_results_match

EVENTS_PER_FRAME = 224
GRID_OPTS = dict(formulation="matmul", voting="nearest", quantized=True,
                 keyframe_dist_frac=0.03)


def _random_segments(rng: np.random.Generator, n: int) -> list[tuple[int, int]]:
    lens = rng.integers(1, 14, size=n)
    starts = np.concatenate([[0], np.cumsum(lens)])
    return [(int(starts[i]), int(starts[i + 1])) for i in range(n)]


def _affine_model(rng: np.random.Generator) -> AffineCostModel:
    return AffineCostModel(params={
        backend: (float(rng.uniform(1e-4, 2e-2)),
                  float(rng.uniform(1e-6, 1e-3)))
        for backend in ("batched", "sharded")})


def _variant_of(s_bucket: int, capacity: int) -> VariantKey:
    return VariantKey(s_bucket=s_bucket, capacity=capacity,
                      backend="batched", interpolation="nearest",
                      quantized=False)


# --- planner: partitions are cost-model-independent -----------------------


@settings(deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(0, 40),
       model_kind=st.sampled_from(["none", "null", "affine"]))
def test_planner_partition_matches_module_planner(seed, n, model_kind):
    """For any cost model, DispatchPlanner.plan is bitwise-equal to
    plan_dispatch_groups (which itself now delegates to a null-model
    planner): the cost model must never change the partition."""
    rng = np.random.default_rng(seed)
    segs = _random_segments(rng, n)
    model = {"none": None, "null": NullCostModel(),
             "affine": _affine_model(rng)}[model_kind]
    planner = DispatchPlanner((1, 2, 4), cost_model=model,
                              variant_of=_variant_of)
    assert planner.plan(segs) == plan_dispatch_groups(segs, 4)


@settings(deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(0, 30),
       n_tags=st.integers(1, 4),
       fairness=st.sampled_from(["fifo", "round_robin"]),
       model_kind=st.sampled_from(["none", "affine"]))
def test_planner_tagged_partition_valid_and_fifo_for_any_model(
        seed, n, n_tags, fairness, model_kind):
    """Tagged partitions: bitwise-equal to the module planner, valid S
    buckets, and per-session FIFO preserved — for any cost model and
    both fairness policies."""
    rng = np.random.default_rng(seed)
    segs = _random_segments(rng, n)
    items = [(int(rng.integers(n_tags)), seg) for seg in segs]
    model = None if model_kind == "none" else _affine_model(rng)
    planner = DispatchPlanner((1, 2, 4), cost_model=model,
                              variant_of=_variant_of)
    groups = planner.plan_tagged(items, fairness=fairness)
    assert groups == plan_dispatch_groups_tagged(items, 4, fairness=fairness)
    flat = [it for g, _ in groups for it in g]
    assert sorted(flat) == sorted(items)  # nothing dropped or duplicated
    for g, cap in groups:
        assert 1 <= len(g) <= 4
        assert all(bucket_capacity(e - s) == cap for _, (s, e) in g)
    for tag in set(t for t, _ in items):
        released = [seg for g, _ in groups for t, seg in g if t == tag]
        arrived = [seg for t, seg in items if t == tag]
        assert released == arrived, "per-session FIFO violated"


def test_planner_validation_and_prediction():
    with pytest.raises(ValueError, match="non-empty"):
        DispatchPlanner(())
    with pytest.raises(ValueError, match="ascending"):
        DispatchPlanner((4, 2, 1))
    planner = DispatchPlanner((1, 2, 4))
    assert planner.s_bucket(3) == 4
    with pytest.raises(ValueError, match="exceeds top"):
        planner.s_bucket(5)
    # no model, no variant factory -> predictions are None (null planner)
    assert planner.predict_group_s(2, 8) is None
    model = AffineCostModel(params={"batched": (0.01, 1e-4)})
    priced = DispatchPlanner((1, 2, 4), cost_model=model,
                             variant_of=_variant_of)
    # padded rows are charged: a group of 3 pads to the 4-bucket
    assert priced.predict_group_s(3, 8) == pytest.approx(0.01 + 1e-4 * 32)
    assert priced.predict_drain_s([(0, (0, 8)), (0, (8, 16))]) == (
        pytest.approx(0.01 + 1e-4 * 2 * 8))
    # one unpredictable group poisons the whole drain estimate
    sharded_only = AffineCostModel(params={"sharded": (0.01, 1e-4)})
    blind = DispatchPlanner((1, 2, 4), cost_model=sharded_only,
                            variant_of=_variant_of)
    assert blind.predict_drain_s([(0, (0, 8))]) is None


# --- cost table: schema, round-trip, atomic persistence -------------------


def test_cost_table_roundtrip_and_stats(tmp_path):
    table = CostTable()
    key = _variant_of(2, 8)
    for wall in (0.010, 0.030, 0.020):
        table.record(key, wall)
    stats = table.entry_stats(key)
    assert stats["count"] == 3
    assert stats["mean_s"] == pytest.approx(0.020)
    assert stats["min_s"] == 0.010 and stats["max_s"] == 0.030
    path = tmp_path / "cost_table.json"
    table.save(str(path))
    loaded = CostTable.load(str(path))
    assert loaded.mean_s(key) == pytest.approx(0.020)
    assert len(loaded) == 1
    # merge folds samples count-weighted
    other = CostTable()
    other.record(key, 0.040)
    loaded.merge(other)
    assert loaded.entry_stats(key)["count"] == 4
    assert loaded.mean_s(key) == pytest.approx(0.025)


@pytest.mark.parametrize("mutate, match", [
    (lambda p: p.update(schema_version=99), "schema version"),
    (lambda p: p.update(entries="nope"), "not an object"),
    (lambda p: p["entries"].update({"bad-key": {"count": 1, "mean_s": 1.0,
                                                "min_s": 1.0, "max_s": 1.0}}),
     "malformed variant key"),
    (lambda p: next(iter(p["entries"].values())).pop("mean_s"),
     "missing fields"),
    (lambda p: next(iter(p["entries"].values())).update(count=0),
     "invalid count"),
    (lambda p: next(iter(p["entries"].values())).update(min_s=9.0),
     "min <= mean <= max"),
])
def test_cost_table_schema_validation_rejects(mutate, match):
    table = CostTable()
    table.record(_variant_of(1, 4), 0.01)
    payload = json.loads(json.dumps(table.to_json()))
    mutate(payload)
    with pytest.raises(CostTableError, match=match):
        CostTable.from_json(payload)


def test_variant_key_validation():
    with pytest.raises(CostTableError, match="backend"):
        VariantKey(1, 4, "gpu", "nearest", False)
    with pytest.raises(CostTableError, match="interpolation"):
        VariantKey(1, 4, "batched", "cubic", False)
    with pytest.raises(CostTableError, match="s_bucket"):
        VariantKey(0, 4, "batched", "nearest", False)
    key = VariantKey(2, 8, "sharded", "bilinear", True)
    assert key.rows == 16
    assert VariantKey.from_str(key.to_str()) == key
    with pytest.raises(CostTableError, match="malformed"):
        VariantKey.from_str("s2/c8/sharded/bilinear")


# --- cost model: fit, fallback, calibration -------------------------------


def test_affine_fit_recovers_exact_affine_data():
    table = CostTable()
    for s in (1, 2, 4):
        for c in (4, 8, 12):
            key = _variant_of(s, c)
            table.record(key, 0.005 + 3e-4 * key.rows)
    model, report = fit_affine_model(table)
    overhead, rate = model.params["batched"]
    assert overhead == pytest.approx(0.005, abs=1e-9)
    assert rate == pytest.approx(3e-4, abs=1e-12)
    assert report["backends"]["batched"]["max_rel_error"] < 1e-9
    # prediction clamps at zero outside the support
    assert model.predict_sweep_s(_variant_of(1, 4)) >= 0.0
    assert model.predict_sweep_s(
        VariantKey(1, 4, "sharded", "nearest", False)) is None


def test_table_model_prefers_measured_over_fallback():
    table = CostTable()
    measured = _variant_of(2, 8)
    table.record(measured, 0.5)  # far off any affine trend
    fallback = AffineCostModel(params={"batched": (0.01, 1e-5)})
    model = TableCostModel(table=table, fallback=fallback)
    assert model.predict_sweep_s(measured) == pytest.approx(0.5)
    out_of_dist = _variant_of(4, 16)
    assert model.predict_sweep_s(out_of_dist) == pytest.approx(
        fallback.predict_sweep_s(out_of_dist))
    assert NullCostModel().predict_sweep_s(measured) is None


def test_calibrate_dry_run_smoke(capsys):
    assert calibrate_main(["--dry-run"]) == 0
    out = capsys.readouterr().out
    assert "dry run OK" in out


# --- replayer: determinism, policies, SLO ---------------------------------


def _burst(n: int, cap: int, *, tag=0, t: float = 0.0) -> list[Arrival]:
    return [Arrival(t=t, tag=tag, seg=(k * cap, (k + 1) * cap))
            for k in range(n)]


def test_replay_latency_vs_throughput_schedules():
    model = AffineCostModel(params={"batched": (0.01, 1e-4)})
    planner = planner_for(model, (1, 2, 4), backend="batched")
    arrivals = _burst(8, 4)
    lat = replay_schedule(arrivals, planner, ReplayConfig(policy="latency"))
    tp = replay_schedule(arrivals, planner, ReplayConfig(policy="throughput"))
    assert lat.dispatch_count == 8
    assert tp.dispatch_count == 2  # two full 4-buckets
    # per-sweep overhead is why coalescing wins throughput
    assert tp.makespan_s < lat.makespan_s
    # determinism: same inputs, identical schedule
    again = replay_schedule(arrivals, planner,
                            ReplayConfig(policy="throughput"))
    assert again.to_json() == tp.to_json()


def test_replay_rejects_unpredictable_variants():
    planner = planner_for(AffineCostModel(params={"sharded": (0.01, 1e-4)}),
                          (1, 2, 4), backend="batched")
    with pytest.raises(ValueError, match="cannot predict"):
        replay_schedule(_burst(2, 4), planner, ReplayConfig(policy="latency"))
    with pytest.raises(ValueError, match="cost model"):
        replay_schedule(_burst(1, 4),
                        DispatchPlanner((1, 2, 4)),
                        ReplayConfig(policy="latency"))


def test_percentile_nearest_rank():
    assert percentile([], 0.99) == 0.0
    assert percentile([3.0, 1.0, 2.0], 0.5) == 2.0
    assert percentile([3.0, 1.0, 2.0], 0.99) == 3.0
    with pytest.raises(ValueError):
        percentile([1.0], 0.0)


@settings(deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 24),
       flush_after=st.floats(0.0, 2.0),
       d_lo=st.floats(1e-3, 5.0), d_hi=st.floats(1e-3, 5.0))
def test_slo_monotone_on_burst_traces(seed, n, flush_after, d_lo, d_hi):
    """Burst-scoped SLO monotonicity: all segments arrive at t=0 and
    flush comes at t>=0, so the partition is fixed by the full queue and
    only WHEN held groups dispatch varies with the deadline — tightening
    `target_latency_s` can then only dispatch earlier, never later, so
    the replayed predicted p99 never increases. (General traces do NOT
    satisfy this — eager dispatch under overload splits coalescible
    groups and pays more total overhead — which is why the property and
    the CI gate are burst-scoped.)"""
    rng = np.random.default_rng(seed)
    model = _affine_model(rng)
    planner = planner_for(model, (1, 2, 4), backend="batched")
    # runs of same-capacity segments, all arriving at t=0
    arrivals = []
    frame = 0
    for seg_len in rng.integers(1, 14, size=n):
        arrivals.append(Arrival(t=0.0, tag=0,
                                seg=(frame, frame + int(seg_len))))
        frame += int(seg_len)
    tight, loose = sorted((d_lo, d_hi))
    p99 = {}
    for d in (tight, loose):
        res = replay_schedule(arrivals, planner, ReplayConfig(
            policy="adaptive", target_latency_s=d, flush_t=flush_after))
        p99[d] = res.predicted_p99_s()
    assert p99[tight] <= p99[loose] + 1e-12, (
        f"tightening the deadline {loose} -> {tight} RAISED predicted "
        f"p99: {p99[loose]} -> {p99[tight]}")


def test_check_slo_burst_gate_passes_on_synthetic_table():
    from repro.profiling.calibrate import synthesize_table

    record = check_slo_burst(synthesize_table(), backend="batched")
    slo, tp = record["slo_adaptive"], record["throughput"]
    assert slo["dispatch_count"] <= tp["dispatch_count"]
    assert slo["predicted_p99_s"] <= record["target_latency_s"] + 1e-12
    # the burst actually coalesces — a degenerate per-segment schedule
    # would make the gate vacuous
    assert tp["dispatch_count"] < record["segments"]


# --- live engines: SLO + profiler end to end ------------------------------


@pytest.fixture(scope="module")
def planning_scene(cam, small_scene):
    ev = small_scene["events"]
    traj = small_scene["traj"]
    n = int(ev.t.shape[0])
    keep = min(n, 13 * EVENTS_PER_FRAME + 32)
    ev = EventStream(xy=ev.xy[:keep], t=ev.t[:keep],
                     polarity=ev.polarity[:keep], valid=ev.valid[:keep])
    frames = aggregate(cam, ev, traj, events_per_frame=EVENTS_PER_FRAME)
    dsi_cfg = DSIConfig.for_camera(cam, num_planes=12, z_min=0.6, z_max=4.5)
    ref = run_emvs(cam, dsi_cfg, frames, EMVSOptions(**GRID_OPTS))
    return ev, traj, ref, dsi_cfg


def _run_burst(engine, ev):
    engine.push(next(iter_event_chunks(ev, int(ev.t.shape[0]))))
    return engine.flush()


def _schedule_fingerprint(stats: dict) -> dict:
    return {k: stats[k] for k in ("segments", "dispatches",
                                  "coalesced_dispatches",
                                  "coalesced_segments", "padded_segments",
                                  "max_pending")}


def test_null_model_slo_schedule_is_bitwise_identical(cam, planning_scene):
    """target_latency_s with a null cost model (or no model at all) must
    leave the adaptive schedule — counters and results — exactly as the
    pre-SLO engine produced it: the depth-based fallback."""
    ev, traj, ref, dsi_cfg = planning_scene
    cfg = dict(events_per_frame=EVENTS_PER_FRAME, dispatch_policy="adaptive")
    base = EMVSStreamEngine(cam, dsi_cfg, traj, EMVSOptions(**GRID_OPTS),
                            StreamConfig(**cfg))
    res_base = _run_burst(base, ev)
    for extra in ({"cost_model": None},
                  {"cost_model": NullCostModel()}):
        engine = EMVSStreamEngine(
            cam, dsi_cfg, traj, EMVSOptions(**GRID_OPTS),
            StreamConfig(**cfg, target_latency_s=0.050), **extra)
        res = _run_burst(engine, ev)
        _assert_results_match(res, res_base, exact_dsi=True)
        assert (_schedule_fingerprint(engine.stats)
                == _schedule_fingerprint(base.stats))
        assert engine.stats["slo_dispatches"] == 0
        assert engine.stats["slo_holds"] == 0
    _assert_results_match(res_base, ref, exact_dsi=True)


def test_slo_adaptive_with_model_stays_bitwise_and_counts(cam,
                                                          planning_scene):
    """A real cost model + deadline changes WHEN groups dispatch (the
    SLO counters must show it) but never the numbers: results stay
    bitwise-equal to offline run_emvs."""
    ev, traj, ref, dsi_cfg = planning_scene
    model = AffineCostModel(params={"batched": (1e-3, 1e-6),
                                    "sharded": (1e-3, 1e-6)})
    for target, expect in ((1e-6, "slo_dispatches"), (10.0, "slo_holds")):
        engine = EMVSStreamEngine(
            cam, dsi_cfg, traj, EMVSOptions(**GRID_OPTS),
            StreamConfig(events_per_frame=EVENTS_PER_FRAME,
                         dispatch_policy="adaptive", target_latency_s=target),
            cost_model=model)
        res = _run_burst(engine, ev)
        _assert_results_match(res, ref, exact_dsi=True)
        assert engine.stats[expect] > 0, (
            f"target={target}: expected {expect} > 0, got {engine.stats}")


def test_profiler_records_trace_and_warm_samples(cam, planning_scene):
    """The opt-in recorder captures a coherent dispatch trace (every
    dispatched segment arrived first) and only warm, unshadowed wall
    times enter the cost table."""
    ev, traj, ref, dsi_cfg = planning_scene
    profiler = SweepProfiler()
    engine = EMVSStreamEngine(
        cam, dsi_cfg, traj, EMVSOptions(**GRID_OPTS),
        StreamConfig(events_per_frame=EVENTS_PER_FRAME,
                     dispatch_policy="latency"),
        profiler=profiler)
    # every variant needs one sweep dispatched onto an idle device for
    # its cold compile to be skipped (checked below): let the burst's
    # sweeps finish before the flush dispatches the tail's variant
    engine.push(next(iter_event_chunks(ev, int(ev.t.shape[0]))))
    for inf in list(engine._inflight):
        jax.block_until_ready(inf.dms.depth)
    engine.flush()
    trace = profiler.trace_json()
    arrived = {(a["tag"], tuple(a["seg"])) for a in trace["arrivals"]}
    dispatched = [(tag, tuple(seg)) for d in trace["dispatches"]
                  for tag, seg in d["segs"]]
    assert len(trace["arrivals"]) == engine.stats["segments"]
    assert len(trace["dispatches"]) == engine.stats["dispatches"]
    assert set(dispatched) <= arrived
    assert len(dispatched) == len(set(dispatched)), "segment dispatched twice"
    for d in trace["dispatches"]:
        VariantKey.from_str(d["key"])  # keys are schema-valid
    # warm samples: the first observation per variant (cold compile) is
    # skipped, so sample count <= dispatches - distinct variants
    total = sum(profiler.table.entry_stats(k)["count"]
                for k in profiler.table.keys())
    assert total + profiler.skipped_cold + profiler.skipped_shadowed == sum(
        1 for _ in trace["dispatches"])
    assert profiler.skipped_cold >= len(set(d["key"]
                                            for d in trace["dispatches"]))
    # and a model fitted from live samples predicts every live variant
    if len(profiler.table):
        model = model_from_table(profiler.table)
        for key in profiler.table.keys():
            assert model.predict_sweep_s(key) is not None


def test_stream_config_target_latency_validation():
    with pytest.raises(ValueError, match="target_latency_s"):
        StreamConfig(target_latency_s=0.0)
    with pytest.raises(ValueError, match="target_latency_s"):
        StreamConfig(target_latency_s=-1.0)
    assert StreamConfig(target_latency_s=0.25).target_latency_s == 0.25
