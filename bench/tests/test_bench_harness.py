"""What the harness refuses, and what ``BENCHMARK.json`` holds."""

import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from work import pack_unpack_bytes  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def tpu(kind="TPU v5 lite"):
    return SimpleNamespace(platform="tpu", device_kind=kind)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.BenchError, match="no peaks"):
        run.chips([tpu("TPU v99 imaginary")], 1)


def test_no_tpu_is_an_error():
    with pytest.raises(run.BenchError, match="no TPU"):
        run.chips([SimpleNamespace(platform="cpu", device_kind="cpu")], 1)
    with pytest.raises(run.BenchError, match="no TPU"):
        run.chips([], 1)


def test_too_few_chips_is_an_error():
    with pytest.raises(run.BenchError, match="needs 4 chips"):
        run.chips([tpu()], 4)
    assert len(run.chips([tpu()] * 4, 4)) == 4


def run_bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "halo3d-512.r1",
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def assert_no_result(proc):
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout
    assert not proc.stdout.strip().startswith("{")


def test_run_without_a_tpu_exits_nonzero_and_prints_no_metric():
    assert_no_result(run_bench(ROOT))


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = run_bench(tmp_path, {"PYTHONPATH": ""})
    assert_no_result(proc)
    assert "the program is not at" in proc.stderr


def test_needed_bytes_of_pack_and_unpack():
    assert pack_unpack_bytes(131072, 8, 1) == 4 << 20
    assert pack_unpack_bytes(2048, 512, 1) == 4 << 20
    assert pack_unpack_bytes(512, 8, 1) == 4 * 4096
    assert pack_unpack_bytes(16, 2, 4) == 4 * 128


def test_benchmark_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for w in SPEC["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200


def test_every_cell_loads_with_its_files():
    for w in SPEC["workloads"]:
        cell = run.load_cell(w["name"], SPEC)
        assert (BENCH / "drivers" / f"{cell.traffic['driver']}.py").is_file()
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert (BENCH / "layer_metrics" / f"{m['name']}.py").is_file()


def test_per_layer_metrics_move_a_metric_their_cells_report():
    for m in SPEC["per_layer"]:
        for name in m["workloads"]:
            reported = {e["name"] for e in run.load_cell(name, SPEC).end_to_end}
            assert m["moves"] in reported


def test_the_check_budget_fits_with_24_cells():
    per_run = SPEC["run_seconds"] + 60
    total = (2 + 14 * 24) * per_run + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_traffic_keys_a_driver_does_not_read_are_refused(tmp_path):
    import jax

    for name in ("halo3d-512.r1", "pack2d.1MiB-b8"):
        cell = run.load_cell(name)
        driver = run.load_module(BENCH / "drivers" /
                                 f"{cell.traffic['driver']}.py")
        with pytest.raises(ValueError, match="clients"):
            driver.Session(cell.config, dict(cell.traffic, clients=4), 1,
                           jax.devices()[:1], tmp_path, 1.0)


def test_halo_config_cycle_is_the_smoothers():
    cycle_ops = run.load_module(BENCH / "drivers" / "halo_program.py").cycle_ops
    cfg = json.loads((BENCH / "configs" / "halo3d-512.json").read_text())
    ops = cycle_ops(cfg)
    assert [o.radii for o in ops] == [(2, 1, 1), (1, 1, 1)]
    with pytest.raises(ValueError):
        cycle_ops(dict(cfg, cycle=list(reversed(cfg["cycle"]))))


def test_config_files_state_their_source_and_assumptions():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("bench/configs/")
        assert cfg["source"] in c["source"] and cfg["reduced"] == c["reduced"]
        assert cfg["assumed"] and cfg["guarantees"]
    for w in SPEC["workloads"]:
        traffic = run.load_cell(w["name"], SPEC).traffic
        if "decomposition" in traffic:
            assert math.prod(traffic["decomposition"]) == w["chips"]
