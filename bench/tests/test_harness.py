"""The whole harness on the host CPU at a tiny size: the result line's
schema, `correct` false under each fault of the timed path, and refusal
to run without a TPU."""
import json
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from conftest import BENCH, CPU_PEAKS, ROOT, tiny
from harness import cell as cell_lib

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELL = "davis240.fleet8.overload"


def run_tiny(bench_spec, tmp_path, seed=2**31 + 77, traced=False):
    import jax

    config, mix = tiny()
    return cell_lib.run_cell(bench_spec, CELL, config, mix, seed, 3.0, traced,
                             jax.devices()[:1], time.perf_counter(), tmp_path,
                             log=lambda m: None, peaks=CPU_PEAKS)


def test_benchmark_json_follows_the_contract(bench_spec):
    assert set(bench_spec) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in bench_spec["configs"]]
    cells = [w["name"] for w in bench_spec["workloads"]]
    metrics = [m["name"] for m in bench_spec["end_to_end"] + bench_spec["per_layer"]]
    for n in names + cells + metrics:
        assert NAME.match(n), n
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)
    assert len(set(metrics)) == len(metrics)
    for c in bench_spec["configs"]:
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).exists()
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] and cfg["name"] == c["name"]
    for w in bench_spec["workloads"]:
        assert w["config"] in names and w["chips"] in (1, 4)
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        assert len(w["why"]) <= 200
    e2e = {m["name"] for m in bench_spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench_spec["end_to_end"] + bench_spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        cell_lib.reader(m["name"])  # every metric has a reader
        for w in m.get("workloads", []):
            assert w in cells
    for m in bench_spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench_spec["per_layer"]:
        assert m["moves"] in e2e and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        for w in m["workloads"]:  # each listed cell reports what it moves
            assert m["moves"] in [
                e["name"] for e in bench_spec["end_to_end"]
                if w in e.get("workloads", [w])]
    for w in cells:
        reported = cell_lib.cell_metrics(bench_spec, w, False)
        assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
        assert cell_lib.cell_metrics(bench_spec, w, True)
    assert len(json.dumps(bench_spec)) < 64 * 1024


@pytest.fixture(scope="module")
def sound(bench_spec, tmp_path_factory):
    return run_tiny(bench_spec, tmp_path_factory.mktemp("sound"))


def test_result_line_schema(sound):
    assert list(sound)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(sound)
    assert sound["correct"] is True and sound["failed"] == 0
    assert sound["attempted"] > 0
    assert set(sound["metrics"]) == {"mev_s", "setup_s"}
    for m in sound["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert sound["device"]["platform"] == "cpu" and sound["device"]["count"] == 1
    for c in sound["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.dumps(sound)


def _broken(fault):
    """The served sweep with one fault planted under it."""
    from repro.core import pipeline

    real = pipeline.process_segments_batched

    def sweep(cam, dsi_cfg, batch, opts):
        if fault == "state_unchanged":  # the DSI never takes a vote
            batch = batch._replace(frame_valid=batch.frame_valid * 0)
        elif fault == "half_left_out":  # half of each segment's frames dropped
            c = batch.frame_valid.shape[1]
            batch = batch._replace(frame_valid=batch.frame_valid.at[:, c // 2:].set(0))
        dsis, dms = real(cam, dsi_cfg, batch, opts)
        if fault == "answer_altered":  # every depth 5% off where it is made
            dms = dms._replace(depth=dms.depth * 1.05)
        return dsis, dms

    return sweep


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_a_broken_sweep_is_not_correct(bench_spec, tmp_path, monkeypatch, fault):
    from repro.serving import sweep_dispatcher

    monkeypatch.setattr(sweep_dispatcher, "process_segments_batched",
                        _broken(fault))
    out = run_tiny(bench_spec, tmp_path)
    assert out["correct"] is False
    failing = [n for n, c in out["checks"].items() if c["value"] > c["limit"]]
    assert failing, out["checks"]


def test_refuses_to_run_without_a_tpu(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path)}
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
