"""Tests of the benchmark's own code, on the host CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import copy
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

CPU_PEAKS = {"cpu": {"ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def load(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


@pytest.fixture(scope="session")
def bench_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny(config_name="davis240", mix_name="fleet8.overload"):
    """A cell's configuration and mix cut to what the host CPU runs in
    seconds: 8 planes, 2 cameras, a low rate."""
    config = copy.deepcopy(load("configs", config_name))
    config["dsi"]["num_planes"] = 8
    mix = dict(load("traffic", mix_name), cameras=2, rate_ev_s=30000,
               packet_events=16384, packet_s=1.0, warmup_s=1.5, check_segments=2,
               speed_m_s=1.0)
    return config, mix
