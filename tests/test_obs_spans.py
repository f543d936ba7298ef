"""Program spans: ``obs.span`` off and on, the profiler sink, and the span
sets of one eager ``WisdomKernel`` launch and of each ``ServeEngine``
decode step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import runtime


@pytest.fixture(autouse=True)
def _obs_off():
    runtime.disable()
    yield
    runtime.disable()


def _spans(tr, prefix=""):
    """Complete events whose name starts with ``prefix``, in start order."""
    return sorted((e for e in tr.events
                   if e["ph"] == "X" and e["name"].startswith(prefix)),
                  key=lambda e: (e["ts"], -e["dur"]))


def _inside(child, parent) -> bool:
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


def _children(events, parent, prefix):
    return [e["name"] for e in events
            if e is not parent and e["name"].startswith(prefix)
            and _inside(e, parent)]


# ------------------------------- the switch ----------------------------------

def test_span_off_is_the_shared_noop_and_records_nothing():
    first = obs.span("launch", cat="kernel")
    assert first is obs.span("serve.step") is runtime._NO_SPAN
    with obs.span("serve.admit") as args:
        assert args is None
    assert runtime.tracer() is None and runtime.metrics() is None


def test_span_on_records_nested_spans_with_late_args():
    _, tr = runtime.enable()
    with obs.span("serve.step", cat="serve") as outer:
        with obs.span("serve.admit", cat="serve", slot=3) as inner:
            inner["requests"] = [7, 8]
        outer["steps"] = 1
    admit, step = tr.events                   # recorded as each one ends
    assert (step["name"], admit["name"]) == ("serve.step", "serve.admit")
    assert step["cat"] == "serve" and step["args"] == {"steps": 1}
    assert admit["args"] == {"requests": [7, 8], "slot": 3}
    assert _inside(admit, step)
    assert tr.profiler is False


def test_profiler_sink_leaves_counters_off():
    reg, tr = runtime.enable(profiler=True)
    assert reg is None and runtime.metrics() is None
    assert tr is runtime.tracer() and tr.profiler
    runtime.disable()
    reg, tr = runtime.enable()
    assert reg is not None and not tr.profiler


def test_profiler_sink_puts_spans_in_the_host_plane():
    from jax._src.lib import _profiler
    from jax.profiler import ProfileData
    runtime.enable(profiler=True)
    session = _profiler.ProfilerSession(_profiler.ProfileOptions())
    try:
        with obs.span("launch", cat="kernel") as args:
            with obs.span("launch.dispatch", cat="kernel"):
                jax.block_until_ready(jnp.ones(8) + 1)
            args["kernel"] = "advec_u"
    finally:
        xspace = session.stop()
    found = {}
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("launch", "launch.dispatch"):
                    found[e.name] = (plane.name, e.start_ns, e.end_ns,
                                     {k: v for k, v in e.stats})
    assert set(found) == {"launch", "launch.dispatch"}
    plane, start, end, stats = found["launch"]
    assert plane.startswith("/host:")
    assert stats == {"kernel": "advec_u"}
    _, c_start, c_end, _ = found["launch.dispatch"]
    assert start <= c_start <= c_end <= end
    assert [e["name"] for e in runtime.tracer().events] == [
        "launch.dispatch", "launch"]


# ------------------------------- the launcher --------------------------------

@pytest.fixture()
def kernel(tmp_path, monkeypatch):
    monkeypatch.setenv("KERNEL_LAUNCHER_WISDOM_DIR", str(tmp_path))
    from repro.core import WisdomKernel, get_kernel
    return WisdomKernel(get_kernel("matmul"), wisdom_dir=tmp_path,
                        device_kind="tpu-v5e", backend="reference")


def test_one_eager_launch_is_one_launch_span_with_its_phases(kernel):
    from repro.prof import Profiler
    a = np.ones((64, 64), np.float32)
    _, tr = runtime.enable()
    kernel(a, a)                  # a miss: builds and compiles
    kernel(a, a)                  # a hit, the one before it still in flight
    kernel.attach_profiler(Profiler(sample_every=1))
    kernel(a, a)                  # a hit the profiler reads: waits for it
    kernel.attach_profiler(None)
    kernel(a, a)                  # a hit with nothing in flight
    launches = [e for e in _spans(tr, "launch") if e["name"] == "launch"]
    assert len(launches) == 4
    events = _spans(tr, "launch.")
    assert [_children(events, e, "launch.") for e in launches] == [
        ["launch.select", "launch.build", "launch.compile",
         "launch.dispatch"],
        ["launch.select", "launch.build", "launch.wait", "launch.dispatch"],
        ["launch.select", "launch.build", "launch.wait", "launch.dispatch",
         "launch.wait"],
        ["launch.select", "launch.build", "launch.dispatch"]]
    assert [e["args"]["cached"] for e in launches] == [False, True, True,
                                                       True]
    assert [e["args"]["waited"] for e in launches] == [False, False, True,
                                                       False]
    assert set(launches[1]["args"]) == {
        "kernel", "tier", "scenario", "cached", "waited", "compile_us",
        "launch_us"}
    for e, st in zip(launches, kernel.stats):
        assert st.launch_s == st.dispatch_s + st.wait_s > 0
        assert e["args"]["launch_us"] == round(st.launch_s * 1e6, 3)
    assert not hasattr(kernel.stats[-1], "load_s")


@pytest.fixture()
def waits(monkeypatch):
    """For each ``jax.block_until_ready`` call, the ids of the arrays it
    waited for."""
    calls = []
    real = jax.block_until_ready

    def counted(x):
        calls.append(_ids(x))
        return real(x)
    monkeypatch.setattr(jax, "block_until_ready", counted)
    return calls


def _ids(tree) -> set[int]:
    return {id(x) for x in jax.tree_util.tree_leaves(tree)}


def test_a_hit_with_no_latency_consumer_does_not_wait_for_its_result(
        kernel, waits):
    from repro.prof import Profiler
    rng = np.random.default_rng(3)
    a, b = (rng.standard_normal((64, 64)).astype(np.float32)
            for _ in range(2))
    first = kernel(a, b)
    assert waits == []
    got = kernel(a, b)
    # it waited for the launch before it, which was still in flight
    assert waits == [_ids(first)]
    st = kernel.stats[-1]
    assert st.cached and not st.waited
    assert st.wait_s == 0.0 and st.launch_s == st.dispatch_s
    kernel.attach_profiler(Profiler(sample_every=1))
    want = kernel(a, b)
    assert waits[1:] == [_ids(got), _ids(want)] and kernel.stats[-1].waited
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_each_kernel_keeps_one_launch_in_flight(tmp_path, waits):
    from repro.core import WisdomKernel, get_kernel
    k1, k2 = (WisdomKernel(get_kernel("matmul"), wisdom_dir=tmp_path,
                           device_kind="tpu-v5e", backend="reference")
              for _ in range(2))
    a = np.ones((64, 64), np.float32)
    outs = [k1(a, a), k2(a, a), k1(a, a), k1(a, a), k2(a, a)]
    assert waits == [_ids(outs[i]) for i in (0, 2, 1)]


def test_an_output_donated_before_the_next_launch_is_not_waited_for(
        kernel, waits):
    a = np.ones((64, 64), np.float32)
    out = kernel(a, a)
    jax.jit(lambda x: x * 2, donate_argnums=0)(out)
    assert out.is_deleted()
    np.testing.assert_array_equal(np.asarray(kernel(a, a)), a @ a)
    assert waits == [set()]


class _Tuner:
    """The online tuner's launch hooks, recording the latency they read."""

    def __init__(self):
        self.launch_s = []

    def before_launch(self, problem, dtype, meta, config, tier):
        return None

    def after_launch(self, problem, dtype, config, tier, launch_s):
        self.launch_s.append(launch_s)


@pytest.mark.parametrize("consumer", ["online", "profiler"])
def test_a_latency_consumer_makes_the_launch_wait(kernel, waits, consumer):
    from repro.prof import Profiler
    a = np.ones((64, 64), np.float32)
    kernel(a, a)
    if consumer == "online":
        tuner = _Tuner()
        kernel.attach_online(tuner)
        want = [True, True, True, True]
    else:
        # due on the first launch after attaching, then every second one
        kernel.attach_profiler(Profiler(sample_every=2))
        want = [True, False, True, False]
    outs = [kernel(a, a) for _ in range(4)]
    stats = kernel.stats[1:]
    assert [s.waited for s in stats] == want
    assert all(_ids(out) in waits for out, waited in zip(outs, want)
               if waited)
    for s in stats:
        assert s.cached
        assert s.launch_s == s.dispatch_s + s.wait_s
        assert (s.wait_s > 0) == s.waited
    if consumer == "online":
        assert tuner.launch_s == [s.launch_s for s in stats]
    else:
        assert len(kernel.profiler.profiles) == 2


def test_the_launch_wait_counter_counts_waited_and_async_launches(kernel):
    from repro.obs.metrics import parse_series
    from repro.prof import Profiler
    a = np.ones((64, 64), np.float32)
    reg, _ = runtime.enable()
    for _ in range(3):
        kernel(a, a)
    kernel.attach_profiler(Profiler(sample_every=2))
    for _ in range(3):
        kernel(a, a)
    got = {}
    for key, n in reg.snapshot()["counters"].items():
        name, labels = parse_series(key)
        if name == "launch.wait":
            got[(labels["kernel"], labels["outcome"])] = n
    assert got == {("matmul", "async"): 4, ("matmul", "waited"): 2}


def test_a_launch_that_raises_is_marked_and_not_counted(kernel, monkeypatch):
    from repro.obs import snapshot_from_trace

    def fail(fn, meta):
        raise RuntimeError("compile failed")

    monkeypatch.setattr(kernel, "_compile", fail)
    a = np.ones((64, 64), np.float32)
    _, tr = runtime.enable()
    with pytest.raises(RuntimeError, match="compile failed"):
        kernel(a, a)
    launch, = [e for e in tr.events if e["name"] == "launch"]
    assert launch["args"] == {"kernel": "matmul",
                              "scenario": "tpu-v5e|64x64x64|float32",
                              "error": "RuntimeError"}
    compile_, = [e for e in tr.events if e["name"] == "launch.compile"]
    assert compile_["args"] == {"error": "RuntimeError"}
    assert kernel.stats == []
    snap = snapshot_from_trace(tr.to_chrome())
    assert not any(k.startswith("select.tier") for k in snap["counters"])


def test_traced_launches_record_no_launch_span(kernel):
    a = jnp.ones((64, 64), jnp.float32)
    _, tr = runtime.enable()
    jax.jit(lambda x: kernel(x, x))(a)
    assert _spans(tr, "launch") == []
    assert kernel.tier_counts == {"default": 1} and kernel.stats == []


def test_eager_executable_is_named_after_the_kernel(kernel):
    a = np.ones((64, 64), np.float32)
    kernel(a, a)
    (compiled,) = kernel.compile_cache._cache.values()
    assert "jit_matmul" in compiled.as_text().splitlines()[0]


# ------------------------------- the serve loop ------------------------------

class _ToyModel:
    """Decode-only model: next token = (tok + 1) mod vocab; with
    ``start`` in its cache the engine runs it in token mode."""

    vocab = 13

    def __init__(self, token_mode: bool):
        self.decode_supports_start = token_mode

    def init_cache(self, n_slots, max_seq):
        cache = {"pos": jnp.zeros((), jnp.int32)}
        if self.decode_supports_start:
            cache["start"] = jnp.zeros((n_slots,), jnp.int32)
        return cache

    def decode_step(self, params, cache, tok):
        logits = jax.nn.one_hot((tok[:, 0] + 1) % self.vocab,
                                self.vocab)[:, None]
        return logits, dict(cache, pos=cache["pos"] + 1)


class _EveryOther:
    """A step profiler that samples the even steps."""

    def __init__(self):
        self.sampled = 0

    def bind(self, params, n_slots, max_seq):
        pass

    def due(self, step):
        return step % 2 == 0

    def on_step(self, step_us):
        self.sampled += 1


@pytest.mark.parametrize("mode", ["token", "cohort"])
def test_each_decode_step_is_one_serve_step_span(mode):
    from repro.serve import Request, ServeEngine
    prof = _EveryOther()
    eng = ServeEngine(_ToyModel(mode == "token"), params={}, n_slots=2,
                      max_seq=32, profiler=prof)
    assert eng.mode == mode
    for rid in range(5):
        eng.submit(Request(rid, np.array([1, 2, 3], np.int32),
                           max_new_tokens=2 + rid % 3))
    _, tr = runtime.enable(profiler=True)   # no profiler session: no-op
    out = eng.run()
    assert out.requests_completed == 5
    events = _spans(tr, "serve.")
    steps = [e for e in events if e["name"] == "serve.step"]
    assert len(steps) == eng.steps_run == out.steps
    waits = 0
    for step in steps:
        names = _children(events, step, "serve.")
        assert names.count("serve.sample") == 1
        for phase in ("serve.dispatch", "serve.tick", "serve.advance"):
            assert names.count(phase) == 1, (phase, names)
        waits += names.count("serve.wait")
        assert names.index("serve.dispatch") < names.index("serve.sample")
    assert waits == prof.sampled == (eng.steps_run + 1) // 2
    outer = "serve.arena" if mode == "token" else "serve.cohort"
    assert sum(e["name"] == outer for e in events) == out.cohorts
    admits = [e for e in events if e["name"] == "serve.admit"]
    if mode == "token":
        admitted = [r for e in admits for r in e["args"]["requests"]]
        assert sorted(admitted) == list(range(5))
    else:
        assert admits == []
