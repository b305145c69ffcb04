"""Device time by the program's named scopes: the innermost-operation
split on synthetic traces, the loader on a trace recorded here on the
CPU, the scope metrics' readers, and the HLO comparison."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import scopes  # noqa: E402
import tracereduce as tr  # noqa: E402
from run import load_module  # noqa: E402

CLASSES = tr.OpClasses.load()
CPU = tr.OpClasses(device_plane="^/host:CPU$", op_lines=("XLA.*Client",),
                   classes=CLASSES.classes)
SCOPE_METRICS = json.loads((BENCH / "scope_metrics.json").read_text())


def test_innermost_scope_of_an_op_name_path():
    assert scopes.innermost(
        "jit(step)/tempi.pack/tempi.bytes/while/body/mul") == "tempi.bytes"
    assert scopes.innermost("jit(step)/shard_map/add") == ""


def test_a_while_and_its_body_count_once():
    # a tempi.bytes loop inside a tempi.pack op: the body's time is the
    # body's scope, the loop's own time outside its body is the loop's
    ops = [(0, 100, "tempi.pack"), (10, 40, "tempi.bytes"),
           (50, 60, "tempi.bytes"), (60, 70, "")]
    assert scopes.innermost_ns(ops, 0, 100) == {
        "tempi.pack": 50, "tempi.bytes": 40, "": 10}


def test_a_body_whose_scope_differs_from_its_while():
    ops = [(0, 50, "tempi.wire"), (5, 45, "tempi.bytes"),
           (10, 20, "tempi.bytes"), (20, 30, "tempi.unpack")]
    assert scopes.innermost_ns(ops, 0, 50) == {
        "tempi.wire": 10, "tempi.bytes": 30, "tempi.unpack": 10}


def test_ops_that_start_together_credit_the_shorter():
    ops = [(0, 10, "outer"), (0, 4, "inner")]
    assert scopes.innermost_ns(ops, 0, 10) == {"inner": 4, "outer": 6}


def synthetic(devices=2):
    t = tr.Trace()
    t.host = [(1000, 2000, "bench.window"), (0, 1000, "bench.setup")]
    for d in range(devices):
        t.devices[f"/device:TPU:{d}"] = [
            (900, 1200, "tempi.stencil"),  # clipped to 1000..1200
            (1200, 1500, "tempi.wire"),
            (1250, 1300, "tempi.bytes"),
            (1600, 1900, "tempi.splice"),
            (1900, 1950 + 10 * d, ""),
        ]
    return t


def overlapping():
    """Operations that overlap without nesting, as on a line of
    asynchronous copies."""
    t = tr.Trace(host=[(0, 100, "bench.window")])
    t.devices["/device:TPU:0"] = [(0, 30, "tempi.pack"), (20, 60, ""),
                                  (50, 80, "tempi.wire"), (90, 99, "")]
    t.devices["/device:TPU:1"] = [(10, 20, "tempi.unpack")]
    return t


@pytest.mark.parametrize("make", [synthetic, overlapping,
                                  lambda: synthetic(1)])
def test_scoped_and_unscoped_time_add_up_to_busy(make):
    t = make()
    times = scopes.scope_times(t)
    assert sum(times["scope_s"].values()) + times["unscoped_s"] == \
        pytest.approx(times["busy_s"], rel=1e-12)
    assert times["busy_s"] == pytest.approx(tr.reduce(t, CLASSES)["busy_s"])


def test_scope_times_of_the_synthetic_trace():
    times = scopes.scope_times(synthetic())
    assert times["scope_s"] == {
        "tempi.bytes": pytest.approx(50e-9),
        "tempi.splice": pytest.approx(300e-9),
        "tempi.stencil": pytest.approx(200e-9),
        "tempi.wire": pytest.approx(250e-9),
    }
    assert times["unscoped_s"] == pytest.approx(55e-9)


def test_a_trace_without_scopes_is_all_unscoped():
    raw = json.loads((BENCH / "tests" / "data" / "halo_r1_slice.json")
                     .read_text())
    t = tr.Trace(host=[tuple(h) for h in raw["host"]])
    for plane, ops in raw["devices"].items():
        t.devices[plane] = [(s, e, scopes.innermost(n)) for s, e, n in ops]
    times = scopes.scope_times(t)
    assert times["scope_s"] == {}
    assert times["unscoped_s"] == pytest.approx(times["busy_s"])
    assert times["busy_s"] == pytest.approx(tr.reduce(t, CLASSES)["busy_s"])


def test_an_event_takes_its_instructions_scope():
    hlo = ('HloModule jit_step, is_scheduled=true\n\n'
           'ENTRY %main.2 (p: f32[8]) -> f32[8] {\n'
           '  %fusion.5 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
           'metadata={op_name="jit(step)/tempi.stencil/add"}\n'
           '  ROOT %copy.1 = f32[8]{0} copy(%fusion.5)\n}\n')
    other = hlo.replace("jit_step", "jit_other").replace(
        "tempi.stencil", "tempi.wire")
    by_module, by_name = scopes.hlo_scopes([hlo, other])
    assert by_module[("jit_step", "fusion.5")] == "tempi.stencil"
    assert by_module[("jit_other", "fusion.5")] == "tempi.wire"
    assert "fusion.5" not in by_name  # the modules disagree
    assert by_name["copy.1"] == ""
    # a v5e event: the instruction's HLO text, no module stat
    assert scopes._event_scope(
        "%copy.1 = f32[8]{0} copy(f32[8]{0} %fusion.5)", [], by_module,
        by_name) == ""
    # a CPU event: the instruction's name and its module
    assert scopes._event_scope(
        "fusion.5", [("hlo_module", "jit_other")], by_module, by_name) == \
        "tempi.wire"
    assert scopes._event_scope("while.9", [], by_module, by_name) is None


def test_compiler_made_instructions_take_the_scope_downstream():
    # an expanded bitcast-convert (convert, reshape) feeding a scoped
    # fusion, an inserted copy feeding a loop, and a loop body whose
    # instructions lost their metadata
    hlo = """HloModule jit_pack, is_scheduled=true

%body.1 (p.1: (s32[], u32[8])) -> (s32[], u32[8]) {
  %p.1 = (s32[], u32[8]) parameter(0)
  %gte.1 = u32[8]{0} get-tuple-element(%p.1), index=1
  %or.1 = u32[8]{0} or(%gte.1, %gte.1)
  ROOT %tuple.1 = (s32[], u32[8]) tuple(%gte.0, %or.1)
}

ENTRY %main.4 (b.1: u8[32]) -> u32[8] {
  %b.1 = u8[32]{0} parameter(0), metadata={op_name="b"}
  %convert.12 = u32[32]{0} convert(%b.1)
  %reshape.37 = u32[8,4]{1,0} reshape(%convert.12)
  %fusion.2 = u32[8]{0} fusion(%reshape.37), kind=kLoop, calls=%fused.1, metadata={op_name="jit(f)/tempi.pack/tempi.bytes/bitcast_convert_type"}
  %copy.5 = u32[8]{0} copy(%fusion.2)
  %while.2 = (s32[], u32[8]) while(%copy.5), condition=%cond.1, body=%body.1, metadata={op_name="jit(f)/tempi.wire/while"}
  ROOT %copy-done = u32[8]{0} copy(%while.2)
}
"""
    by_module, _ = scopes.hlo_scopes([hlo])
    scope = {name: s for (module, name), s in by_module.items()}
    assert scope["convert.12"] == scope["reshape.37"] == "tempi.bytes"
    assert scope["copy.5"] == "tempi.wire"
    assert scope["or.1"] == scope["gte.1"] == "tempi.wire"
    assert scope["copy-done"] == ""


def test_load_reads_scopes_and_program_host_spans(tmp_path):
    """A trace recorded on the CPU, the CPU's plane standing in for a
    device: each operation carries the scope of its instruction, and
    the program's tracer spans land beside the harness's."""
    import jax
    import jax.numpy as jnp

    from repro.obs import Tracer, scope

    @jax.jit
    def f(x):
        with scope("pack"):
            y = jnp.sin(x) @ x
            with scope("bytes"):
                y = jax.lax.bitcast_convert_type(y, jnp.uint32)
        return y

    x = jnp.ones((128, 128))
    hlo = f.lower(x).compile().as_text()
    f(x).block_until_ready()
    tracer = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with tracer.span("exchange"):
                    f(x).block_until_ready()
    t, unmatched = scopes.load(tmp_path, CPU, [hlo])
    labels = {label for _, _, label in t.devices["/host:CPU"]}
    assert labels & {"tempi.pack", "tempi.bytes"}
    names = [n for _, _, n in t.host]
    assert names.count("bench.window") == 1
    assert names.count("tempi.exchange") == 3
    times = scopes.scope_times(t)
    assert sum(times["scope_s"].values()) + times["unscoped_s"] == \
        pytest.approx(times["busy_s"])


def test_a_program_host_span_names_a_gap():
    host = [(0, 100, "bench.window"), (40, 80, "bench.block"),
            (45, 75, "tempi.exchange")]
    assert tr.label_gap((50, 70), host) == "tempi.exchange"
    assert tr.label_gap((76, 79), host) == "bench.block"


def _ctx(times, **extra):
    ctx = dict(window_s=2e-3, busy_s=times["busy_s"], calls=4,
               work_units=12, **extra)
    ctx.update(times)
    return ctx


@pytest.mark.parametrize("entry", SCOPE_METRICS["per_layer"],
                         ids=lambda m: m["name"])
def test_scope_metric_readers(entry):
    reader = load_module(BENCH / "layer_metrics" / f"{entry['name']}.py")
    times = {"scope_s": {"tempi.stencil": 6e-3, "tempi.splice": 1.2e-3,
                         "tempi.pack": 3e-4, "tempi.unpack": 6e-4,
                         "tempi.wire": 1.2e-4, "tempi.bytes": 2.4e-3},
             "unscoped_s": 1e-4}
    times["busy_s"] = sum(times["scope_s"].values()) + times["unscoped_s"]
    name = entry["name"]
    if name.startswith("unscoped_share."):
        want = 100 * 1e-4 / times["busy_s"]
        absent = dict(times, scope_s={})
    else:
        scope = "tempi." + name.split("_ms.")[0]
        per = 12 if name.endswith(".halo") else 4
        want = times["scope_s"][scope] / per * 1e3
        absent = dict(times, scope_s={k: v for k, v in
                                      times["scope_s"].items() if k != scope})
    assert reader.read(_ctx(times)) == pytest.approx(want)
    assert reader.read(_ctx(absent)) is None
    # the harness's own context, which carries no scope times yet
    assert reader.read(dict(window_s=1.0, busy_s=0.5, calls=4,
                            work_units=12)) is None


def test_scope_metric_entries_have_the_benchmark_form():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"]} | {"stencil"}
    existing = {m["name"] for m in bench["per_layer"]}
    for m in SCOPE_METRICS["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["name"] not in existing and m["layer"] in layers
        assert m["source"] == "device_trace" and m["better"] == "lower"
        assert set(m["workloads"]) <= cells
        assert set(m["workloads"]) <= set(e2e[m["moves"]]["workloads"])
        assert (BENCH / "layer_metrics" / f"{m['name']}.py").is_file()


def _kernel_body(name: str, path: str) -> str:
    """A serialized kernel module named ``name`` whose op carries a
    location in ``path``, as a Pallas TPU custom call's ``body``."""
    import base64
    import io

    from jax._src.lib.mlir import ir

    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(
            f'module @{name} {{ "k.copy"() : () -> () loc("{path}":3:1) }}')
        out = io.BytesIO()
        module.operation.write_bytecode(out)
    return base64.b64encode(out.getvalue()).decode()


HLO = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "SRC/ops.py"

%fused_computation.3 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %sine.1 = f32[8]{0} sine(%param_0), metadata={op_name="jit(step)/SCOPE/sin"}
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %fusion.4 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.3
  ROOT %KERNEL.2 = f32[8]{0} custom-call(%fusion.4), custom_call_target="tpu_custom_call", backend_config={"custom_call_config":{"body":"BODY"}}, metadata={op_name="jit(step)/SCOPE/pallas_call"}
}
"""


def _hlo(scope: str, kernel: str, src: str, op: str = "sine") -> str:
    return (HLO.replace("SCOPE", scope).replace("KERNEL", kernel)
            .replace("SRC", src).replace("sine(", f"{op}(")
            .replace("BODY", _kernel_body(kernel, src)))


def test_strip_hlo_ignores_scopes_kernel_names_and_sources():
    parent = _hlo("", "_lambda_", "/a/parent/src")
    change = _hlo("tempi.pack", "tempi_pack_rows", "/b/src")
    assert parent != change
    assert scopes.strip_hlo(parent) == scopes.strip_hlo(change)
    assert scopes.strip_hlo(parent) != scopes.strip_hlo(
        _hlo("", "_lambda_", "/a/parent/src", op="cosine"))


def test_compare_hlo(tmp_path, capsys):
    import scope_breakdown

    a, b = tmp_path / "a", tmp_path / "b"
    for d, text in ((a, _hlo("", "_lambda_", "/a")),
                    (b, _hlo("tempi.pack", "tempi_pack_rows", "/b"))):
        d.mkdir()
        (d / "step.hlo.txt").write_text(text)
    assert scope_breakdown.compare_hlo(a, b) == 0
    assert "identical after stripping" in capsys.readouterr().out
    (b / "step.hlo.txt").write_text(_hlo("", "_lambda_", "/a", op="cosine"))
    assert scope_breakdown.compare_hlo(a, b) == 1


def test_breakdown_gives_the_readers_scope_times(tmp_path, monkeypatch):
    """The traced run of ``scope_breakdown.py`` is the harness's, with
    ``scope_s`` and ``unscoped_s`` added to the readers' context and
    the harness's reduction put back afterwards."""
    from types import SimpleNamespace

    import run
    import scope_breakdown

    t = synthetic()
    monkeypatch.setattr(tr, "load", lambda trace_dir, classes: t)
    monkeypatch.setattr(scopes, "load",
                        lambda trace_dir, classes, texts: (t, 0))
    seen = {}
    monkeypatch.setattr(run, "load_module", lambda path: SimpleNamespace(
        read=lambda ctx: seen.update(ctx) or 1.0))
    cell = run.Cell("halo3d-512.r1", 1, {}, {"driver": "d"}, [],
                    [{"name": "stencil_ms.halo", "unit": "ms"}], {})
    step = SimpleNamespace(as_text=lambda: "HloModule m")
    session = SimpleNamespace(facts={}, work_units=lambda n: 3 * n,
                              layer_context=lambda: {}, step=step)
    reduce = tr.reduce
    wrapped = scope_breakdown.with_scopes(run.layer_metrics, tmp_path / "hlo")
    (tmp_path / "trace").mkdir()
    metrics, breakdown = wrapped(cell, session, tmp_path / "trace", 2,
                                 [SimpleNamespace(device_kind="TPU v5 lite")])
    assert tr.reduce is reduce
    assert metrics == {"stencil_ms.halo": {"value": 1.0, "unit": "ms"}}
    assert seen["scope_s"] == scopes.scope_times(t)["scope_s"]
    assert seen["unscoped_s"] == pytest.approx(55e-9)
    assert breakdown["busy_s"] == pytest.approx(tr.reduce(t, CLASSES)["busy_s"])
    assert (tmp_path / "hlo" / "step.hlo.txt").read_text() == "HloModule m"
    names = [m["name"] for m in scope_breakdown.scope_metrics("halo3d-512.r1")]
    assert "stencil_ms.halo" in names and "bytes_ms.call" not in names
