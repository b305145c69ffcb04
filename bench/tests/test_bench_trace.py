"""The reduction from a profiler trace to layer metrics, on synthetic
traces and on one recorded here on the CPU."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import tracereduce as tr  # noqa: E402
from run import load_module  # noqa: E402

CLASSES = tr.OpClasses.load()


def test_union_merges_overlaps_and_clips():
    ops = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (38, 50, "d")]
    assert tr.union(ops, 2, 45) == [(2, 20), (30, 45)]
    assert tr.busy_ns(ops, 2, 45) == 18 + 15


def test_nested_ops_count_once_in_busy_time():
    ops = [(0, 100, "while"), (10, 20, "fusion"), (50, 60, "fusion")]
    assert tr.busy_ns(ops, 0, 100) == 100


def test_gaps_cover_the_rest_of_the_window():
    ops = [(10, 20, "a"), (30, 40, "b")]
    assert tr.gaps(ops, 0, 50) == [(0, 10), (20, 30), (40, 50)]
    assert tr.gaps([], 0, 5) == [(0, 5)]
    window = 50
    assert tr.busy_ns(ops, 0, 50) + sum(e - s for s, e in tr.gaps(ops, 0, 50)) == window


def test_gap_is_named_by_the_innermost_host_span():
    host = [(0, 100, "bench.window"), (40, 60, "bench.dispatch"),
            (60, 90, "bench.block")]
    assert tr.label_gap((42, 58), host) == "bench.dispatch"
    assert tr.label_gap((61, 70), host) == "bench.block"
    assert tr.label_gap((200, 300), host) == "none"


@pytest.mark.parametrize("name, label", [
    ("all-to-all.3 all-to-all u8[4096]", "collective"),
    ("ragged-all-to-all ragged-all-to-all u8[4096,128]", "collective"),
    ("collective-permute-start.1 collective-permute-start (u8[45230208]",
     "collective"),
    ("collective-permute-done collective-permute-done u8[45230208]",
     "collective"),
    ("all-gather.2 all-gather f32[8]", "collective"),
    ("fusion.12 fusion f32[512,512,512]", None),
    ("copy.4 copy u32[9,6,6]", None),
    ("step.3 custom-call u32[9,6,6]", "pallas"),
])
def test_op_classes(name, label):
    assert CLASSES.classify(name) == label


def synthetic(devices=2):
    t = tr.Trace()
    t.host = [(1000, 2000, "bench.window"), (1000, 1100, "bench.dispatch"),
              (1100, 2000, "bench.block"), (0, 1000, "bench.setup")]
    for d in range(devices):
        t.devices[f"/device:TPU:{d}"] = [
            (900, 1200, "fusion.1 fusion f32[8]"),  # clipped to 1000..1200
            (1200, 1500, "all-to-all.1 all-to-all f32[8]"),
            (1600, 1900, "fusion.1 fusion f32[8]"),
        ]
    return t


def test_reduce_synthetic_trace():
    r = tr.reduce(synthetic(), CLASSES)
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(800e-9)
    assert r["class_s"] == {"collective": pytest.approx(300e-9)}
    assert r["device_ops"][0] == ["fusion.1 fusion f32[8]",
                                  pytest.approx(500e-9)]
    assert r["idle_gaps"][0] == ["bench.block", pytest.approx(100e-9)]


def test_reduce_without_a_device_plane_fails():
    t = synthetic()
    t.devices = {}
    with pytest.raises(ValueError):
        tr.reduce(t, CLASSES)


def layer_ctx(**extra):
    ctx = dict(tr.reduce(synthetic(), CLASSES), calls=4, work_units=12,
               peaks={"hbm_bytes_per_s": 819e9})
    ctx.update(extra)
    return ctx


def read(metric, ctx):
    return load_module(BENCH / "layer_metrics" / f"{metric}.py").read(ctx)


def test_layer_metric_readers():
    ctx = layer_ctx(needed_bytes_per_call=4 << 20)
    assert read("idle_share.halo", ctx) == pytest.approx(20.0)
    assert read("idle_share.call", ctx) == pytest.approx(20.0)
    assert read("collective_ms.halo", ctx) == pytest.approx(300e-9 / 12 * 1e3)
    busy_per_call = 800e-9 / 4
    assert read("pack_hbm_share.call", ctx) == pytest.approx(
        100 * (4 << 20) / 819e9 / busy_per_call)


def test_collective_reader_finds_nothing_without_collectives():
    ctx = layer_ctx()
    ctx["class_s"] = {}
    assert read("collective_ms.halo", ctx) is None


def test_load_reads_a_recorded_trace(tmp_path):
    """A trace recorded on the CPU, read with the CPU's plane standing in
    for a device: the harness's host spans and the operations land."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    y = f(x)
                with jax.profiler.TraceAnnotation("bench.block"):
                    y.block_until_ready()
    cpu = tr.OpClasses(device_plane="^/host:CPU$", op_lines=("XLA.*Client",),
                       classes=CLASSES.classes)
    t = tr.load(tmp_path, cpu)
    assert list(t.devices) == ["/host:CPU"]
    names = [n for _, _, n in t.host]
    assert names.count("bench.window") == 1
    assert names.count("bench.dispatch") == 3
    lo, hi = t.span("bench.window")
    assert hi > lo
    ops = t.devices["/host:CPU"]
    assert ops and all(e >= s for s, e, _ in ops)
    assert 0 < tr.busy_ns(ops, lo, hi) <= hi - lo


def recorded_slice():
    import json

    raw = json.loads((BENCH / "tests" / "data" / "halo_r1_slice.json")
                     .read_text())
    t = tr.Trace(host=[tuple(h) for h in raw["host"]])
    for plane, ops in raw["devices"].items():
        t.devices[plane] = [(s, e, tr.short_name(n)) for s, e, n in ops]
    return raw, t


def test_reduce_recorded_chip_trace_slice():
    raw, t = recorded_slice()
    r = tr.reduce(t, CLASSES)
    lo, hi = t.span("bench.window")
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    # the collective ops by their own opcode, never the fusions that
    # read their results
    own = sum(min(e, hi) - max(s, lo)
              for s, e, n in raw["devices"]["/device:TPU:0"]
              if n.startswith("%collective-permute") and e > lo and s < hi)
    assert own > 0
    assert r["class_s"]["collective"] == pytest.approx(own * 1e-9)
    assert all(" = " not in name for name, _ in r["device_ops"])


def test_short_name_keeps_instruction_opcode_and_shape():
    assert tr.short_name(
        "%fusion.5 = f32[520,518,518]{2,1,0:T(8,128)} fusion(f32[530,524,524]"
        "{2,1,0} %collective-permute-done.1), kind=kLoop"
    ) == "fusion.5 fusion f32[520,518,518]"
    assert CLASSES.classify(tr.short_name(
        '%step.77 = u32[9,6,6]{2,1,0:T(8,128)S(1)} custom-call(u32[530,524,524]'
        '{2,1,0:T(8,128)} %bitcast_convert_type.242), custom_call_target='
        '"tpu_custom_call"')) == "pallas"
    assert tr.short_name("jit_step(123)") == "jit_step(123)"


def test_drive_traces_the_start_of_the_window(tmp_path, monkeypatch):
    """A traced window records its first ``TRACE_SECONDS`` inside one
    ``bench.window`` span, and goes on calling to the end."""
    import time

    import jax.numpy as jnp

    import run

    class Session:
        def call(self):
            jnp.ones(8).block_until_ready()
            time.sleep(0.01)
            return 0.0

    monkeypatch.setattr(run, "TRACE_SECONDS", 0.1)
    latencies, window, traced = run.drive(Session(), 0.4, tmp_path)
    assert 0 < traced < len(latencies) and window >= 0.4
    cpu = tr.OpClasses(device_plane="^/host:CPU$", op_lines=("XLA.*Client",),
                       classes=CLASSES.classes)
    lo, hi = tr.load(tmp_path, cpu).span("bench.window")
    assert 0.1e9 <= hi - lo < 0.4e9


def test_drive_leaves_the_checks_own_seconds_out_of_the_window():
    import time

    import run

    class Session:
        def call(self):
            time.sleep(0.02)
            return 0.015

    t0 = time.perf_counter()
    latencies, window, _ = run.drive(Session(), 0.2)
    wall = time.perf_counter() - t0
    assert window >= 0.2
    assert window == pytest.approx(sum(latencies), abs=0.01)
    assert wall - window == pytest.approx(0.015 * len(latencies), abs=0.01)


def test_layer_metric_readers_see_the_cell_and_the_plan(tmp_path,
                                                        monkeypatch):
    """A reader gets the reduced trace with the cell's configuration and
    traffic and the session's plan facts, so a new metric needs only its
    own file."""
    from types import SimpleNamespace

    import run

    _, t = recorded_slice()
    monkeypatch.setattr(tr, "load", lambda trace_dir, classes: t)
    seen = {}
    monkeypatch.setattr(run, "load_module", lambda path: SimpleNamespace(
        read=lambda ctx: seen.update(ctx) or 1.0))
    cell = run.Cell("c", 1, {"interior": [8, 8, 8]}, {"driver": "d"}, [],
                    [{"name": "m", "unit": "%"}], {})
    session = SimpleNamespace(facts={"steps": 3}, work_units=lambda n: 3 * n,
                              layer_context=lambda: {"extra": 1})
    (tmp_path / "trace").mkdir()
    metrics, _ = run.layer_metrics(cell, session, tmp_path / "trace", 2,
                                   [SimpleNamespace(device_kind="TPU v5 lite")])
    assert metrics == {"m": {"value": 1.0, "unit": "%"}}
    assert seen["config"] == {"interior": [8, 8, 8]}
    assert seen["traffic"] == {"driver": "d"}
    assert seen["facts"] == {"steps": 3} and seen["extra"] == 1
    assert seen["calls"] == 2 and seen["work_units"] == 6
    assert seen["busy_s"] > 0 and seen["peaks"]["hbm_bytes_per_s"] > 0
