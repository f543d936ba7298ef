"""The reduction from a profiler trace to device metrics: on a synthetic
trace whose answer is known, and on a small trace recorded on a TPU v5e.

The recorded trace is the ``xspace`` of a ``harness.Window(0.02,
trace=True)`` driven by the stencil driver at 128³ float32 (two steps,
the second traced)."""

from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path

import pytest

from bench import trace_reduce

FIXTURE = Path(__file__).with_name("fixtures") / "stencil128.xspace"


@dataclass
class Ev:
    name: str
    start_ns: float
    end_ns: float


@dataclass
class Line:
    name: str
    events: list = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)


@dataclass
class Profile:
    planes: list


STEP = ["advec_u", "diff_uvw", "jit_stencil_update"]


KERNEL = 'custom_call_target="tpu_custom_call"'


def synthetic(step_modules=STEP, extra=(), diff_op=None
              ) -> trace_reduce.Reduction:
    diff_op = diff_op or f"%run.3 = f32[2] custom-call(%a), {KERNEL}"
    device = Plane("/device:TPU:0", [
        Line("XLA Ops", [Ev("fusion.1", 0, 10),
                         Ev(f"%run.1 = f32[2] custom-call(%u), {KERNEL}",
                            5, 20),
                         Ev(diff_op, 22, 28),
                         Ev("fusion.2", 30, 40), Ev("late", 100, 120)]),
        Line("XLA Modules", [Ev("jit_run(7)", 0, 20),
                             Ev("jit_run(8)", 22, 28), *extra,
                             Ev("jit_stencil_update(9)", 30, 40),
                             Ev("jit_run(7)", 100, 120)])])
    # host spans a little off the device's clock, as on the chip
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 0, 100), Ev("bench.step", -2, 50),
        Ev("bench.launch.advec_u", 5, 25), Ev("bench.update", 25, 45),
        Ev("PjitFunction(f)", 1, 2)])])
    return trace_reduce.reduce_profile(Profile([host, device]), step_modules)


def test_busy_time_is_the_union_of_operations_in_the_window():
    tr = synthetic()
    assert tr.devices == 1
    assert tr.window_s == pytest.approx(100e-9)
    assert tr.busy_s == pytest.approx(36e-9)  # [0, 20], [22, 28], [30, 40]
    assert set(tr.ops) == {"advec_u/fusion.1", "advec_u/run.1",
                           "diff_uvw/run.3", "jit_stencil_update/fusion.2"}
    assert tr.ops["advec_u/run.1"] == [1, pytest.approx(15e-9)]
    assert tr.label_error is None


def test_idle_gaps_go_to_the_innermost_open_span():
    tr = synthetic()
    assert tr.idle_by_span == {"bench.launch.advec_u": pytest.approx(2e-9),
                               "bench.update": pytest.approx(2e-9),
                               "bench.window": pytest.approx(60e-9)}
    b = tr.breakdown()
    assert b["idle_gaps"][0][0] == "bench.window"
    assert [n for n, _ in b["device_ops"]][:1] == ["advec_u/run.1"]


def test_modules_take_the_labels_of_the_step_order():
    tr = synthetic()
    assert trace_reduce.module_seconds(tr, "jit_stencil_update") == \
        (1, pytest.approx(10e-9))
    assert trace_reduce.kernel_seconds({"trace": tr}, "advec_u") == \
        pytest.approx(20e-9)
    assert trace_reduce.kernel_seconds({"trace": tr}, "diff_uvw") == \
        pytest.approx(6e-9)
    assert trace_reduce.kernel_seconds({"trace": None}, "advec_u") is None
    # without an order, modules keep their names
    bare = synthetic(step_modules=None)
    assert trace_reduce.module_seconds(bare, "jit_run") == \
        (2, pytest.approx(26e-9))
    assert trace_reduce.kernel_seconds({"trace": bare}, "advec_u") is None


@pytest.mark.parametrize("case", ["extra_module", "no_kernel"])
def test_modules_of_another_shape_are_not_labelled(case):
    """A launch that gains a module of its own, or a labelled module with
    no Pallas kernel in it, leaves every module its name: the kernels'
    metrics then find nothing rather than another module's time."""
    if case == "extra_module":
        tr = synthetic(extra=[Ev("jit_convert(5)", 28.5, 29.5)])
        assert "3 modules" in tr.label_error
    else:
        tr = synthetic(diff_op="%fusion.3 = f32[2] fusion(%a)")
        assert "diff_uvw" in tr.label_error
    assert trace_reduce.kernel_seconds({"trace": tr}, "advec_u") is None
    assert trace_reduce.kernel_seconds({"trace": tr}, "diff_uvw") is None
    assert trace_reduce.module_seconds(tr, "jit_run")[0] == 2


def test_op_names_are_the_hlo_instruction_names():
    assert trace_reduce.op_name("%run.1 = (f32[8]) custom-call(%u)") == "run.1"
    assert trace_reduce.op_name("fusion") == "fusion"


def test_a_trace_without_a_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce_profile(Profile([Plane("/host:CPU", [])]))


def test_recorded_v5e_trace():
    tr = trace_reduce.reduce(FIXTURE.read_bytes(), STEP)
    assert tr.devices == 1
    assert 0 < tr.busy_s < tr.window_s
    names = {m.name for m in tr.modules}
    assert "jit_stencil_update" in names
    rec = {"trace": tr}
    adv = trace_reduce.kernel_seconds(rec, "advec_u")
    dif = trace_reduce.kernel_seconds(rec, "diff_uvw")
    assert adv and dif and dif > adv
    b = tr.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert {n.split("/")[0] for n, _ in b["device_ops"]} >= {
        "advec_u", "diff_uvw", "jit_stencil_update"}
    assert tr.label_error is None


def test_recorded_v5e_trace_in_another_step_order_is_not_labelled():
    """The recorded step has two kernel modules before the update; an
    order that names one (as if a launch had gained or lost a module)
    labels nothing."""
    tr = trace_reduce.reduce(FIXTURE.read_bytes(),
                             ["advec_u", "jit_stencil_update"])
    assert "where the step order names 1" in tr.label_error
    assert trace_reduce.kernel_seconds({"trace": tr}, "advec_u") is None
    assert trace_reduce.module_seconds(tr, "jit_run")[0] == 2
