"""A trace recorded on a TPU v5e with the program's own spans in it.

``fixtures/stencil128_spans.xspace`` is the ``xspace`` of a
``harness.Window(0.02, trace=True)`` around ``bench/drivers/stencil_loop.py``
at 128³ float32 (two steps, the second traced), with the program's
profiler sink switched on while the profiler recorded
(``repro.obs.enable(profiler=True)`` as the recording started). The
launcher's spans sit inside the benchmark's ``bench.launch.*`` spans,
and its eager programs are named after their kernels. ``bench/program_spans.py``
reads those spans: on that trace, on a synthetic one whose answer is
known, and in a run driven on the CPU."""

from __future__ import annotations

import io
import json
from dataclasses import dataclass, field
from pathlib import Path

import pytest

from bench import program_spans, trace_reduce
from bench_tiny import SERVE_SECONDS

FIXTURE = Path(__file__).with_name("fixtures") / "stencil128_spans.xspace"
STEP = ["advec_u", "diff_uvw", "jit_stencil_update"]
PROGRAM = ("launch", "serve.")
PHASES = ["launch.select", "launch.build", "launch.dispatch", "launch.wait"]


@dataclass
class _Ev:
    name: str
    start_ns: float
    end_ns: float


@dataclass
class _Line:
    name: str
    events: list = field(default_factory=list)


@dataclass
class _Plane:
    name: str
    lines: list


@dataclass
class _Profile:
    planes: list


def _profile(keep_program: bool) -> _Profile:
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(FIXTURE.read_bytes())
    return _Profile([
        _Plane(p.name, [_Line(line.name, [
            e for e in line.events
            if keep_program or not e.name.startswith(PROGRAM)])
            for line in p.lines])
        for p in pd.planes])


def _host_spans(prefixes) -> list[tuple[str, float, float]]:
    out = []
    for plane in _profile(True).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns) for e in line.events
                        if e.name.startswith(prefixes)]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _within(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_program_spans_leave_every_existing_reading_unchanged():
    got = trace_reduce.reduce_profile(_profile(True), STEP)
    want = trace_reduce.reduce_profile(_profile(False), STEP)
    assert got.window == want.window and got.devices == want.devices == 1
    assert got.busy_s == want.busy_s > 0
    assert got.ops == want.ops
    assert [(m.name, m.start, m.end) for m in got.modules] == \
        [(m.name, m.start, m.end) for m in want.modules]
    assert got.idle_by_span == want.idle_by_span
    assert got.breakdown() == want.breakdown()
    assert got.label_error is want.label_error is None
    assert trace_reduce.kernel_seconds({"trace": got}, "diff_uvw") == \
        trace_reduce.kernel_seconds({"trace": want}, "diff_uvw") > 0


def test_eager_programs_carry_their_kernels_names():
    bare = trace_reduce.reduce(FIXTURE.read_bytes())
    names = {m.name for m in bare.modules}
    assert "jit_run" not in names
    assert names <= {"jit_advec_u", "jit_diff_uvw", "jit_stencil_update"}
    assert "jit_diff_uvw" in names
    labelled = trace_reduce.reduce(FIXTURE.read_bytes(), STEP)
    assert {m.name for m in labelled.modules} == {"diff_uvw",
                                                  "jit_stencil_update"}


def test_each_launch_holds_its_phases_in_order():
    spans = _host_spans(("bench.launch.", "launch"))
    outer = [s for s in spans if s[0].startswith("bench.launch.")]
    launches = [s for s in spans if s[0] == "launch"]
    assert len(outer) == len(launches) == 2
    for bench_span, launch in zip(outer, launches):
        assert _within(launch, bench_span)
        phases = [s for s in spans if s[0].startswith("launch.")
                  and _within(s, launch)]
        assert [s[0] for s in phases] == PHASES    # cached: no compile
        for a, b in zip(phases, phases[1:]):
            assert a[2] <= b[1]
        wait = phases[-1]
        host_us = ((launch[2] - launch[1]) - (wait[2] - wait[1])) / 1e3
        assert 0 < host_us < launch[2] - launch[1]


# ------------------------------ program_spans ---------------------------------

def test_program_readings_of_the_recorded_trace():
    ps = program_spans.read(FIXTURE.read_bytes())
    assert [s.name for s in ps.named("launch")] == ["launch", "launch"]
    # (2,233,230 + 2,297,150 ns of launch) less (750,180 + 889,490 of wait)
    assert program_spans.launch_host_us(ps) == pytest.approx(1445.355)
    assert program_spans.host_ms(ps) is program_spans.sample_ms(ps) is None
    assert ps.idle_by_span == pytest.approx({
        "launch.dispatch": 0.00235765, "launch.build": 0.001180705,
        "launch.wait": 2.9e-08, "bench.update": 0.001987066})
    assert ps.program_share() == 1.0
    bench_only = trace_reduce.reduce(FIXTURE.read_bytes())
    assert sum(ps.idle_by_span.values()) == pytest.approx(
        sum(bench_only.idle_by_span.values()))
    assert set(bench_only.idle_by_span) == {"bench.launch.advec_u",
                                            "bench.launch.diff_uvw",
                                            "bench.update"}


def _synthetic_serve() -> program_spans.ProgramSpans:
    device = _Plane("/device:TPU:0", [_Line("XLA Ops", [
        _Ev("fusion.1", 0, 40), _Ev("fusion.2", 60, 140)])])
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("bench.window", 0, 200), _Ev("serve.arena", -50, 250),
        # a step cut by the window's start: not counted
        _Ev("serve.step", -10, 30), _Ev("serve.sample", 20, 29),
        _Ev("serve.step", 30, 100), _Ev("serve.dispatch", 31, 35),
        _Ev("serve.wait", 35, 70), _Ev("serve.sample", 70, 90),
        _Ev("serve.step", 100, 180), _Ev("serve.dispatch", 101, 104),
        _Ev("serve.sample", 105, 155), _Ev("bench.serve.hook", 160, 170),
        _Ev("PjitFunction(f)", 40, 60)])])
    return program_spans.read_profile(_Profile([host, device]))


def test_serve_readings_and_idle_by_innermost_span_on_a_synthetic_trace():
    ps = _synthetic_serve()
    assert [s.name for s in ps.named("serve.step")] == ["serve.step"] * 2
    # steps of 70 and 80 ns, the first waiting 35
    assert program_spans.host_ms(ps) == pytest.approx((35 + 80) / 2 * 1e-6)
    assert program_spans.sample_ms(ps) == pytest.approx((20 + 50) / 2 * 1e-6)
    assert program_spans.launch_host_us(ps) is None
    # gaps 40-60 (inside the wait), 140-200 (hook 160-170 at 170: the step)
    assert ps.idle_by_span == pytest.approx({"serve.wait": 20e-9,
                                             "serve.step": 60e-9})
    assert ps.program_share() == 1.0


def test_a_trace_without_one_window_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        program_spans.read_profile(_Profile([_Plane("/host:CPU", [
            _Line("python", [_Ev("launch", 0, 5)])])]))


@pytest.mark.parametrize("cell,sink,want", [
    ("microhh-256-bf16", 1, ["launch", "launch.build", "launch.dispatch",
                             "launch.select", "launch.wait"]),
    ("microhh-256-bf16", 0, []),
    # an arena generation or an admission may straddle the window's edge
    ("serve-decode-heavy", 1, ["serve.advance", "serve.dispatch",
                               "serve.sample", "serve.step", "serve.tick",
                               "serve.wait"]),
])
def test_a_traced_run_with_the_sink_on_reads_the_programs_spans(
        tiny, cell, sink, want):
    from bench import harness, work

    def cpu(chips):
        import jax
        return jax.devices(), work.Peaks("cpu", 197e12, 819e9)

    seconds = SERVE_SECONDS if cell.startswith("serve") else 0.5
    out = io.StringIO()
    rc = program_spans.main(["--workload", cell, "--seed", "11",
                             "--seconds", str(seconds), "--sink", str(sink)],
                            require_chip=cpu, out=out, err=io.StringIO())
    assert rc == 0 and harness.Window.__qualname__ == "Window"
    *_, result, line = out.getvalue().strip().splitlines()
    assert json.loads(result)["correct"]
    got = json.loads(line)
    assert (got["workload"], got["seed"], got["sink"]) == (cell, 11, sink)
    prefix = "serve." if cell.startswith("serve") else "launch"
    assert set(want) <= set(got["spans"])
    assert all(name.startswith(prefix) for name in got["spans"])
    assert bool(got["spans"]) == bool(sink)
    reading = got["host_ms"] if cell.startswith("serve") \
        else got["launch_host_us"]
    assert (reading is not None and reading > 0) == bool(sink)
    from repro.obs import runtime
    assert runtime.tracer() is None
