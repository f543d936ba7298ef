"""Observability: deterministic snapshots, Chrome traces, wisdom health.

The contracts under test are the ones the fleet health layer and the CI
report job lean on: snapshot JSON round-trips byte-exactly, histogram
bucketing is identical across processes, exported traces satisfy the
Chrome ``trace_event`` schema, the disabled path is a no-op, and the
health report is a pure function of its snapshot.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (COUNT_BUCKETS, DEFAULT_BUCKETS_US, MetricsRegistry,
                       Tracer, load_snapshot, load_trace, merge_snapshots,
                       parse_series, render_report, save_snapshot,
                       scenario_health, series_key, snapshot_bytes,
                       snapshot_from_trace, validate_trace)
from repro.obs import runtime

SRC = str(Path(__file__).resolve().parents[1] / "src")


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with observability disabled."""
    runtime.disable()
    yield
    runtime.disable()


# ------------------------------ metrics --------------------------------------

def test_series_key_roundtrip():
    key = series_key("select.tier", {"kernel": "matmul", "tier": "exact"})
    assert key == "select.tier{kernel=matmul,tier=exact}"
    assert parse_series(key) == ("select.tier",
                                 {"kernel": "matmul", "tier": "exact"})
    assert parse_series("launch.count") == ("launch.count", {})
    with pytest.raises(ValueError):
        series_key("bad{name", {})
    with pytest.raises(ValueError):
        series_key("n", {"k": "a,b"})


def _populated_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    reg.counter("launch.count", kernel="matmul").inc(7)
    reg.gauge("serve.queue_depth").set(3)
    h = reg.histogram("launch.latency_us", kernel="matmul")
    for v in (0.5, 3.0, 999.0, 2_000_000.0):
        h.observe(v)
    return reg


def test_snapshot_save_load_roundtrip(tmp_path):
    reg = _populated_registry()
    snap = reg.snapshot()
    p = save_snapshot(snap, tmp_path / "s.json")
    loaded = load_snapshot(p)
    assert loaded == snap
    assert snapshot_bytes(loaded) == p.read_bytes()
    h = snap["histograms"]["launch.latency_us{kernel=matmul}"]
    assert h["bounds"] == list(DEFAULT_BUCKETS_US)
    assert sum(h["counts"]) == h["count"] == 4
    assert h["counts"][-1] == 1                 # +Inf bucket got 2e6


def test_load_snapshot_rejects_future_version(tmp_path):
    p = tmp_path / "v.json"
    p.write_text(json.dumps({"version": 99, "counters": {}}))
    with pytest.raises(ValueError, match="version 99"):
        load_snapshot(p)
    (tmp_path / "junk.json").write_text("[1,2]")
    with pytest.raises(ValueError):
        load_snapshot(tmp_path / "junk.json")


def test_histogram_bucketing_deterministic_across_processes():
    """Same observations in another interpreter -> byte-identical
    snapshot (fixed declared bounds, no data-dependent bucketing)."""
    values = [0.9, 1.0, 1.1, 47.0, 999.999, 1e7, 0.0]
    reg = MetricsRegistry()
    for v in values:
        reg.histogram("launch.latency_us", kernel="k").observe(v)
    here = snapshot_bytes(reg.snapshot())

    script = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "from repro.obs import MetricsRegistry, snapshot_bytes\n"
        "reg = MetricsRegistry()\n"
        f"for v in {values!r}:\n"
        "    reg.histogram('launch.latency_us', kernel='k').observe(v)\n"
        "sys.stdout.buffer.write(snapshot_bytes(reg.snapshot()))\n")
    out = subprocess.run([sys.executable, "-c", script, SRC],
                         capture_output=True, check=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout == here


def test_histogram_redeclare_with_other_bounds_raises():
    reg = MetricsRegistry()
    reg.histogram("h", COUNT_BUCKETS, kernel="k")
    with pytest.raises(ValueError, match="different bounds"):
        reg.histogram("h", DEFAULT_BUCKETS_US, kernel="k")
    with pytest.raises(ValueError):
        reg.histogram("h2", bounds=(3.0, 1.0))   # not ascending


def test_merge_snapshots_semantics():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("launch.count").inc(2)
    b.counter("launch.count").inc(5)
    a.gauge("serve.queue_depth").set(3)
    b.gauge("serve.queue_depth").set(9)
    a.histogram("h", COUNT_BUCKETS).observe(1)
    b.histogram("h", COUNT_BUCKETS).observe(300)
    merged = merge_snapshots([a.snapshot(), b.snapshot()])
    assert merged["counters"]["launch.count"] == 7       # sum
    assert merged["gauges"]["serve.queue_depth"] == 9    # max
    h = merged["histograms"]["h"]
    assert h["count"] == 2 and h["counts"][0] == 1 and h["counts"][-1] == 1

    c = MetricsRegistry()
    c.histogram("h", DEFAULT_BUCKETS_US).observe(1)
    with pytest.raises(ValueError, match="bounds differ"):
        merge_snapshots([a.snapshot(), c.snapshot()])


# ------------------------------- tracing -------------------------------------

class _FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 0.001
        return self.t


def _scripted_trace() -> Tracer:
    tr = Tracer(clock=_FakeClock())
    with tr.span("launch", cat="kernel", kernel="matmul", tier="exact",
                 scenario="tpu-v5e|8x8|float32"):
        tr.instant("online.promoted", cat="online", kernel="matmul")
    with tr.span("serve.cohort", cat="serve", size=2):
        pass
    return tr


def test_trace_chrome_schema_valid_and_deterministic(tmp_path):
    t1, t2 = _scripted_trace(), _scripted_trace()
    assert validate_trace(t1.to_chrome()) == []
    p = t1.save(tmp_path / "t.json")
    doc = load_trace(p)
    assert doc == t1.to_chrome()
    assert len(t1) == 3
    # injectable clock => byte-determinism across tracer instances
    assert json.dumps(t1.to_chrome(), sort_keys=True) == \
        json.dumps(t2.to_chrome(), sort_keys=True)
    ph = [ev["ph"] for ev in doc["traceEvents"]]
    assert ph == ["i", "X", "X"]             # instant inside the first span


def test_validate_trace_rejects_bad(tmp_path):
    assert validate_trace([]) != []
    assert validate_trace({"traceEvents": [{"name": "x"}]}) != []
    bad = {"traceEvents": [{"name": "x", "cat": "c", "ph": "X", "ts": 0,
                            "pid": 1, "tid": 0, "dur": -5}]}
    assert any("negative" in e for e in validate_trace(bad))
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="not a valid Chrome trace"):
        load_trace(p)


# --------------------------- runtime switch ----------------------------------

def test_disabled_mode_is_noop_and_enable_is_idempotent():
    assert runtime.metrics() is None and runtime.tracer() is None
    assert not runtime.enabled()
    reg, tr = runtime.enable()
    reg2, tr2 = runtime.enable()
    assert reg is reg2 and tr is tr2         # counters survive re-enable
    assert runtime.metrics() is reg
    runtime.disable()
    assert runtime.metrics() is None


def test_launch_instrumentation_and_always_on_tier_tally(wisdom_dir):
    """Disabled: a launch leaves no registry but still tallies tiers on
    the kernel (the satellite API). Enabled: the same launch produces
    select.tier/launch.count series and a trace launch event."""
    from repro.core import WisdomKernel, get_kernel
    a = np.ones((64, 64), np.float32)
    k = WisdomKernel(get_kernel("matmul"), wisdom_dir=wisdom_dir,
                     device_kind="tpu-v5e", backend="reference")
    k(a, a)
    assert k.tier_counts == {"default": 1} and k.last_tier == "default"
    assert runtime.metrics() is None         # stayed disabled

    reg, tr = runtime.enable()
    k(a, a)
    assert k.tier_counts["default"] == 2
    snap = reg.snapshot()
    tier_keys = [s for s in snap["counters"] if s.startswith("select.tier")]
    assert tier_keys == ["select.tier{kernel=matmul,"
                         "scenario=tpu-v5e|64x64x64|float32,tier=default}"]
    assert snap["counters"]["launch.count{kernel=matmul}"] == 1
    assert snap["counters"]["compile.cache{kernel=matmul,outcome=hit}"] == 1
    launches = [ev for ev in tr.events if ev["name"] == "launch"]
    assert len(launches) == 1
    assert launches[0]["args"]["tier"] == "default"
    assert validate_trace(tr.to_chrome()) == []


def test_single_source_of_tier_names():
    """Satellite: core/scenario.py is the one definition — the online
    tracker re-exports the very same objects, and Wisdom.select only
    produces tiers from it."""
    from repro.core import scenario
    from repro.online import tracker
    assert tracker.MISS_TIERS is scenario.MISS_TIERS
    assert tracker.SELECT_TIERS is scenario.SELECT_TIERS
    assert tracker.format_key is scenario.format_key
    assert scenario.SELECT_TIERS[0] == "exact"
    assert scenario.SELECT_TIERS[-1] == "default"
    assert scenario.MISS_TIERS == set(scenario.SELECT_TIERS) - {"exact"}
    key = ("tpu-v5e", (256, 256), "float32")
    assert scenario.parse_key(scenario.format_key(key)) == key


# ------------------------------- report --------------------------------------

def _health_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    sc = "tpu-v5e|256x256x256|float32"
    for tier, n in (("exact", 8), ("device+dtype", 2)):
        reg.counter("select.tier", kernel="matmul", scenario=sc,
                    tier=tier).inc(n)
    reg.counter("select.tier", kernel="attn",
                scenario="tpu-v4|64x64|bfloat16", tier="default").inc(5)
    reg.counter("launch.count", kernel="matmul").inc(10)
    return reg


def test_report_is_pure_and_names_scenarios():
    snap = _health_registry().snapshot()
    r1, r2 = render_report(snap), render_report(snap)
    assert r1 == r2                           # same snapshot, same bytes
    assert "matmul tpu-v5e|256x256x256|float32: hit-rate=0.80" in r1
    assert "attn tpu-v4|64x64|bfloat16: hit-rate=0.00" in r1
    assert "dominant-tier=default" in r1
    health = scenario_health(snap)
    assert [h.kernel for h in health] == ["attn", "matmul"]
    assert health[1].misses == 2 and health[1].launches == 10


def test_snapshot_from_trace_matches_counters():
    tr = _scripted_trace()
    snap = snapshot_from_trace(tr.to_chrome())
    key = ("select.tier{kernel=matmul,scenario=tpu-v5e|8x8|float32,"
           "tier=exact}")
    assert snap["counters"][key] == 1
    assert snap["histograms"]["launch.latency_us{kernel=matmul}"][
        "count"] == 1
    assert "hit-rate=1.00" in render_report(snap)


# ----------------------------- serve stats -----------------------------------

class _ToyModel:
    """Minimal decode-only model: next token = (tok + 1) mod vocab."""

    vocab = 13

    def init_cache(self, n_slots, max_seq):
        return {"pos": jnp.zeros((), jnp.int32)}

    def decode_step(self, params, cache, tok):
        logits = jax.nn.one_hot((tok[:, 0] + 1) % self.vocab,
                                self.vocab)[:, None]
        return logits, {"pos": cache["pos"] + 1}


def test_serve_run_returns_report_with_stats():
    from repro.serve import Request, ServeEngine, ServeReport
    eng = ServeEngine(_ToyModel(), params={}, n_slots=2, max_seq=16)
    for rid in range(4):                      # 4 requests, 2 slots
        eng.submit(Request(rid, np.array([1, 2], np.int32),
                           max_new_tokens=3))
    reg, _ = runtime.enable()
    out = eng.run()
    assert isinstance(out, ServeReport)
    # mapping compatibility with the old {rid: tokens} return value
    assert set(out) == {0, 1, 2, 3} and len(out) == 4
    assert out[0][0] == 3 and 2 in out
    assert sorted(out.keys()) == [0, 1, 2, 3]
    # the new per-run stats
    assert out.cohorts == 2
    assert out.requests_completed == 4
    assert out.steps == eng.steps_run > 0
    assert out.to_json()["cohorts"] == 2
    snap = reg.snapshot()
    assert snap["counters"]["serve.decode_steps"] == out.steps
    assert snap["counters"]["serve.requests_completed"] == 4
    assert snap["histograms"]["serve.cohort_size"]["count"] == 2


# ----------------------------- fleet health ----------------------------------

def test_fleet_health_aggregates_bus_snapshots():
    from repro.distrib.sync import MemoryTransport
    from repro.fleet import ControlBus
    from repro.fleet.health import (MetricsPublisher,
                                    aggregate_fleet_metrics, fleet_health,
                                    fleet_snapshots, publish_metrics)
    bus = ControlBus(MemoryTransport())
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    sc = "tpu-v5e|8x8|float32"
    r1.counter("select.tier", kernel="m", scenario=sc, tier="exact").inc(3)
    r2.counter("select.tier", kernel="m", scenario=sc, tier="default").inc(1)
    publish_metrics(bus, "host-1", r1)
    publish_metrics(bus, "host-2", r2)
    assert sorted(fleet_snapshots(bus)) == ["host-1", "host-2"]
    merged = aggregate_fleet_metrics(bus)
    assert merged["counters"][
        f"select.tier{{kernel=m,scenario={sc},tier=exact}}"] == 3
    text = fleet_health(bus)
    assert f"m {sc}: hit-rate=0.75 launches=4" in text

    with pytest.raises(RuntimeError, match="disabled"):
        publish_metrics(bus, "host-3")       # no registry, obs off

    pub = MetricsPublisher(bus, "host-3", interval=2, registry=r1)
    assert [pub.tick() for _ in range(4)] == [True, False, True, False]
    assert pub.publishes == 2
    assert MetricsPublisher(bus, "h", registry=None).tick() is False


def test_lease_lifecycle_metrics():
    from repro.distrib.sync import MemoryTransport
    from repro.fleet import ControlBus, ManualClock, TuningJob
    from repro.fleet.jobs import (claim_shard, heartbeat, job_id_for,
                                  release)
    reg, _ = runtime.enable()
    bus = ControlBus(MemoryTransport())
    clock = ManualClock()
    key = ("tpu-v5e", (64, 64, 64), "float32")
    job = TuningJob(job_id=job_id_for("matmul", key), kernel="matmul",
                    device_kind="tpu-v5e", problem=(64, 64, 64),
                    dtype="float32", n_shards=2)
    lease = claim_shard(bus, job, "s000", "w1", clock)
    assert lease is not None
    heartbeat(bus, lease, clock)
    assert claim_shard(bus, job, "s000", "w2", clock) is None  # live: no event
    clock.advance(120.0)
    stolen = claim_shard(bus, job, "s000", "w2", clock)        # expired
    assert stolen is not None and stolen.claims == 2
    release(bus, stolen)
    from repro.fleet.jobs import LeaseLost
    with pytest.raises(LeaseLost):
        heartbeat(bus, lease, clock)         # w1's nonce is gone
    c = reg.snapshot()["counters"]
    assert c["fleet.lease{event=acquire,worker=w1}"] == 1
    assert c["fleet.lease{event=heartbeat,worker=w1}"] == 1
    assert c["fleet.lease{event=reclaim,worker=w2}"] == 1
    assert c["fleet.lease{event=release,worker=w2}"] == 1
    assert c["fleet.lease{event=lost,worker=w1}"] == 1


def test_sync_failure_isolated_and_counted(tmp_path):
    from repro.distrib.store import WisdomStore
    from repro.distrib.sync import PullSync

    class _DeadTransport:
        def list_kernels(self):
            raise OSError("mount gone")

        def fetch(self, name):              # pragma: no cover
            return None

        def publish(self, name, doc):       # pragma: no cover
            pass

    reg, _ = runtime.enable()
    sync = PullSync(WisdomStore(tmp_path), _DeadTransport(), interval=1)
    assert sync.tick() is None
    assert sync.failures == 1
    assert reg.snapshot()["counters"][
        "sync.failures{direction=pull}"] == 1


# --------------------------------- CLI ---------------------------------------

def test_cli_report_snapshot_trace(tmp_path, capsys):
    from repro.obs.cli import main
    snap_path = save_snapshot(_health_registry().snapshot(),
                              tmp_path / "s.json")
    assert main(["report", str(snap_path)]) == 0
    first = capsys.readouterr().out
    assert main(["report", str(snap_path)]) == 0
    assert capsys.readouterr().out == first   # byte-deterministic
    assert "Tier breakdown (per kernel)" in first

    trace_path = _scripted_trace().save(tmp_path / "t.json")
    assert main(["trace", str(trace_path)]) == 0
    assert "valid Chrome trace: 3 event(s)" in capsys.readouterr().out

    merged = tmp_path / "merged.json"
    assert main(["snapshot", str(snap_path), str(snap_path),
                 "--out", str(merged)]) == 0
    doc = load_snapshot(merged)
    assert doc["counters"]["launch.count{kernel=matmul}"] == 20  # summed

    bad = tmp_path / "bad-trace.json"
    bad.write_text("{}")
    assert main(["trace", str(bad)]) == 1
