"""Fleet wisdom distribution: versioned stores, merge, sync, CLI.

Covers the ISSUE 2 acceptance criteria: conflicting same-scenario records
merge deterministically to the statistical winner with both provenances
preserved in its lineage (library AND ``python -m repro.wisdom merge``),
equal-time ties resolve identically regardless of input order, files from
a future ``WISDOM_VERSION`` are refused loudly, and v1 files round-trip
through ``migrate``.
"""

import json

import numpy as np
import pytest

from repro.core.wisdom import (WISDOM_VERSION, Wisdom, WisdomRecord,
                               WisdomVersionError, make_provenance,
                               migrate_doc)
from repro.distrib import (DirectoryTransport, MemoryTransport, PullSync,
                           PushSync, WisdomStore, merge_stores, merge_wisdom)
from repro.distrib.cli import main as wisdom_cli


def rec(device="tpu-v5e", family="tpu-v5", problem=(256, 256),
        dtype="float32", score=100.0, config=None, host="hostA",
        strategy="bayes", evals=10):
    prov = make_provenance(strategy=strategy, evals=evals,
                           objective="costmodel")
    prov["host"] = host
    return WisdomRecord(device_kind=device, device_family=family,
                        problem_size=tuple(problem), dtype=dtype,
                        config=config or {"block": 1},
                        score_us=score, provenance=prov)


def store_with(path, *records, kernel="k"):
    store = WisdomStore(path)
    w = Wisdom(kernel)
    for r in records:
        w.add(r, keep_best=False)
    store.save(w)
    return store


# ------------------------------- merge engine --------------------------------

def test_merge_conflict_keeps_faster_and_both_provenances(tmp_path):
    """The acceptance-criteria scenario: two stores, same (device, problem,
    dtype), different configs/scores -> faster wins, lineage holds both."""
    slow = rec(score=100.0, config={"block": 1}, host="hostA")
    fast = rec(score=40.0, config={"block": 8}, host="hostB")
    a = store_with(tmp_path / "a", slow)
    b = store_with(tmp_path / "b", fast)

    report = merge_stores(a, b)
    merged = a.load("k")
    assert len(merged) == 1
    winner = merged.records[0]
    assert winner.config == {"block": 8}
    assert winner.score_us == 40.0
    assert winner.provenance["host"] == "hostB"
    hosts = {e.get("host") for e in winner.lineage}
    assert hosts == {"hostA", "hostB"}          # both provenances preserved
    assert report.conflicts == 1 and report.replaced == 1


def test_merge_is_order_independent(tmp_path):
    records = [rec(score=s, config={"block": i}, host=f"h{i}")
               for i, s in enumerate([50.0, 30.0, 80.0])]
    wisdoms = [Wisdom("k", [r]) for r in records]
    docs = []
    for order in ([0, 1, 2], [2, 1, 0], [1, 0, 2]):
        merged = merge_wisdom(*[wisdoms[i] for i in order])
        docs.append(json.dumps(merged.to_doc(), sort_keys=True))
    assert docs[0] == docs[1] == docs[2]
    assert merge_wisdom(*wisdoms).records[0].config == {"block": 1}


def test_merge_equal_times_tie_breaks_on_evaluations_then_id(tmp_path):
    """Duplicate scenarios with equal measured times: more tuning effort
    wins; with effort also equal the pick is still deterministic."""
    light = rec(score=50.0, config={"block": 1}, host="hA", evals=5)
    heavy = rec(score=50.0, config={"block": 2}, host="hB", evals=500)
    m1 = merge_wisdom(Wisdom("k", [light]), Wisdom("k", [heavy]))
    m2 = merge_wisdom(Wisdom("k", [heavy]), Wisdom("k", [light]))
    assert m1.records[0].config == {"block": 2}        # more evaluations
    assert (json.dumps(m1.to_doc(), sort_keys=True)
            == json.dumps(m2.to_doc(), sort_keys=True))

    # fully-equal stats: winner decided by record_id, same either way
    x = rec(score=50.0, config={"block": 3}, host="hX", evals=5)
    y = rec(score=50.0, config={"block": 4}, host="hY", evals=5)
    w1 = merge_wisdom(Wisdom("k", [x]), Wisdom("k", [y])).records[0]
    w2 = merge_wisdom(Wisdom("k", [y]), Wisdom("k", [x])).records[0]
    assert w1.config == w2.config
    expected = min([x, y], key=lambda r: r.record_id())
    assert w1.config == expected.config


def test_merge_idempotent_and_self_merge_stable(tmp_path):
    a = store_with(tmp_path / "a", rec(score=10.0, config={"block": 1}),
                   rec(problem=(64, 64), score=5.0, config={"block": 2}))
    b = store_with(tmp_path / "b", rec(score=7.0, config={"block": 9},
                                       host="hB"))
    merge_stores(a, b)
    snap = a.load("k").to_doc()
    merge_stores(a, b)                        # merging again changes nothing
    assert a.load("k").to_doc() == snap


def test_merge_refuses_mixed_kernels():
    with pytest.raises(ValueError, match="different kernels"):
        merge_wisdom(Wisdom("k1", [rec()]), Wisdom("k2", [rec()]))


def test_merge_disjoint_kernels_unions(tmp_path):
    a = WisdomStore(tmp_path / "a")
    wa = Wisdom("alpha")
    wa.add(rec())
    a.save(wa)
    b = WisdomStore(tmp_path / "b")
    wb = Wisdom("beta")
    wb.add(rec(config={"block": 3}))
    b.save(wb)
    merge_stores(a, b)
    assert a.kernels() == ["alpha", "beta"]
    assert len(a.load("beta")) == 1


# ---------------------------- schema versioning ------------------------------

def write_doc(path, doc):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(doc))


def test_future_version_refused_loudly(tmp_path):
    store = WisdomStore(tmp_path)
    write_doc(store.path_for("k"), {
        "kernel": "k", "version": WISDOM_VERSION + 1,
        "records": [rec().to_json()]})
    with pytest.raises(WisdomVersionError, match="version "
                       f"{WISDOM_VERSION + 1}"):
        store.load("k")
    with pytest.raises(WisdomVersionError):
        store.migrate()
    # merge must refuse too, not silently drop the records
    dest = store_with(tmp_path / "dest", rec())
    with pytest.raises(WisdomVersionError):
        merge_stores(dest, store)
    # validate reports it instead of raising (complete report semantics)
    issues = store.validate()
    assert len(issues) == 1 and "version" in issues[0].problem


def test_v1_file_migrate_round_trip(tmp_path):
    store = WisdomStore(tmp_path)
    v1_rec = rec(score=12.0, config={"block": 4}).to_json()
    del v1_rec["lineage"]                      # v1 records have no lineage
    write_doc(store.path_for("k"), {"kernel": "k", "version": 1,
                                    "records": [v1_rec]})
    assert store.version_of("k") == 1
    # loading migrates in memory without touching the file
    loaded = store.load("k")
    assert loaded.records[0].lineage == []
    assert store.version_of("k") == 1

    assert store.migrate() == ["k"]
    assert store.version_of("k") == WISDOM_VERSION
    again = store.load("k")
    assert again.records[0].config == {"block": 4}
    assert again.records[0].score_us == 12.0
    assert store.migrate() == []               # idempotent
    assert store.validate() == []


def test_unversioned_doc_counts_as_v1(tmp_path):
    store = WisdomStore(tmp_path)
    v1_rec = rec().to_json()
    del v1_rec["lineage"]
    write_doc(store.path_for("k"), {"kernel": "k", "records": [v1_rec]})
    assert store.version_of("k") == 1
    assert len(store.load("k")) == 1


def test_migrate_doc_refuses_future_and_is_pure():
    doc = {"kernel": "k", "version": 1, "records": [{"device_kind": "d"}]}
    out = migrate_doc(doc)
    assert out["version"] == WISDOM_VERSION
    assert "lineage" in out["records"][0]
    assert "lineage" not in doc["records"][0]      # input untouched
    with pytest.raises(WisdomVersionError):
        migrate_doc({"version": WISDOM_VERSION + 5})


# ------------------------------- store upkeep --------------------------------

def test_store_validate_flags_bad_json_and_mismatch(tmp_path):
    store = WisdomStore(tmp_path)
    store.path_for("broken").parent.mkdir(parents=True, exist_ok=True)
    store.path_for("broken").write_text("{not json")
    store.path_for("listdoc").write_text("[]")     # valid JSON, wrong shape
    write_doc(store.path_for("other"), {"kernel": "different",
                                        "version": WISDOM_VERSION,
                                        "records": []})
    problems = {i.kernel: i.problem for i in store.validate()}
    assert "unreadable JSON" in problems["broken"]
    assert "not a JSON object" in problems["listdoc"]
    assert "does not match" in problems["other"]
    with pytest.raises(ValueError, match="not a JSON object"):
        store.load("listdoc")


def test_store_prune(tmp_path):
    dup_a = rec(score=10.0, config={"block": 1})
    dup_b = rec(score=4.0, config={"block": 2})
    other_dev = rec(device="tpu-v4", family="tpu-v4", score=9.0)
    store = store_with(tmp_path, dup_a, dup_b, other_dev)
    report = store.prune(device_kind="tpu-v5e")
    assert report.total == 2                     # the dup loser + tpu-v4
    kept = store.load("k").records
    assert len(kept) == 1 and kept[0].config == {"block": 2}
    # pruning everything removes the file
    store.prune(device_kind="no-such-device")
    assert store.kernels() == []


def test_provenance_tolerates_host_and_platform_failures(monkeypatch):
    import platform
    import socket

    def boom(*a, **k):
        raise OSError("sandboxed")

    monkeypatch.setattr(socket, "gethostname", boom)
    monkeypatch.setattr(platform, "platform", boom)
    prov = make_provenance(strategy="s", evals=1, objective="o")
    assert prov["host"] == "unknown"
    assert prov["platform"] == "unknown"
    assert prov["strategy"] == "s"


# ----------------------------------- sync ------------------------------------

def test_push_broadcast_pull_round_trip(tmp_path):
    local = store_with(tmp_path / "local", rec(score=10.0,
                                               config={"block": 1}))
    transport = MemoryTransport()
    push = PushSync(local, transport)
    push.push()
    assert transport.list_kernels() == ["k"]

    # a second host broadcasts a faster promotion for the same scenario
    promoted = rec(score=3.0, config={"block": 16}, host="hostB",
                   strategy="online")
    PushSync(WisdomStore(tmp_path / "b"), transport).broadcast("k", promoted)

    puller = store_with(tmp_path / "c", rec(score=8.0, config={"block": 2},
                                            host="hostC"))
    PullSync(puller, transport, interval=1).pull()
    got = puller.load("k").records[0]
    assert got.config == {"block": 16}
    assert {e.get("host") for e in got.lineage} >= {"hostB", "hostC"}


def test_pull_persists_lineage_only_changes(tmp_path):
    """Same winner on both sides, but the fleet copy carries lineage from
    other hosts: the pooled history must be saved locally, not dropped."""
    import dataclasses

    base = rec(score=5.0, config={"block": 1}, host="h1")
    local = store_with(tmp_path / "l", base)
    transport = MemoryTransport()
    remote = dataclasses.replace(
        base, lineage=[{"host": "h2", "date": "2026-01-01T00:00:00+00:00"}])
    transport.publish("k", Wisdom("k", [remote]).to_doc())
    PullSync(local, transport, interval=1).pull()
    got = local.load("k").records[0]
    assert got.record_id() == base.record_id()
    assert any(e.get("host") == "h2" for e in got.lineage)


def test_push_never_clobbers_better_remote(tmp_path):
    transport = MemoryTransport()
    fast = rec(score=2.0, config={"block": 7}, host="fasthost")
    PushSync(store_with(tmp_path / "fast", fast), transport).push()
    slow = rec(score=90.0, config={"block": 1}, host="slowhost")
    PushSync(store_with(tmp_path / "slow", slow), transport).push()
    remote = transport.fetch("k")["records"]
    assert len(remote) == 1 and remote[0]["config"] == {"block": 7}


def test_directory_transport_equivalent_to_memory(tmp_path):
    src = store_with(tmp_path / "src", rec(score=5.0, config={"block": 3}))
    shared = DirectoryTransport(tmp_path / "shared")
    PushSync(src, shared).push()
    dst = WisdomStore(tmp_path / "dst")
    PullSync(dst, shared, interval=1).pull()
    assert (json.dumps(dst.load("k").to_doc(), sort_keys=True)
            == json.dumps(src.load("k").to_doc(), sort_keys=True))


def test_pull_tick_interval_and_kernel_refresh(tmp_path):
    class FakeKernel:
        def __init__(self, name):
            self.builder = type("B", (), {"name": name})()
            self.refreshes = 0

        def refresh_wisdom(self):
            self.refreshes += 1

    transport = MemoryTransport()
    PushSync(store_with(tmp_path / "src", rec(config={"block": 5})),
             transport).push()
    local = WisdomStore(tmp_path / "local")
    kern = FakeKernel("k")
    sync = PullSync(local, transport, kernels=[kern], interval=4)
    for _ in range(8):
        sync.tick()
    assert sync.pulls == 2                      # ticks 0 and 4
    assert kern.refreshes == 1                  # only the changing pull
    assert len(local.load("k")) == 1


class FlakyTransport(MemoryTransport):
    """MemoryTransport that raises on fetches of one kernel until
    ``heal()`` — the shared-mount-hiccup simulator."""

    def __init__(self, fail_on: str):
        super().__init__()
        self.fail_on = fail_on
        self.failing = True

    def heal(self):
        self.failing = False

    def fetch(self, kernel_name):
        if self.failing and kernel_name == self.fail_on:
            raise OSError(f"transport lost mid-pull fetching {kernel_name}")
        return super().fetch(kernel_name)


def test_pull_is_transactional_on_transport_failure(tmp_path):
    """ISSUE 5 satellite: a transport dying mid-pull must leave the local
    store byte-identical — no kernel from earlier in the same pull may
    have been persisted (partial store state)."""
    transport = FlakyTransport(fail_on="bbb")
    transport.publish("aaa", Wisdom("aaa", [rec(config={"block": 2})])
                      .to_doc())
    transport.publish("bbb", Wisdom("bbb", [rec(config={"block": 3})])
                      .to_doc())
    local = WisdomStore(tmp_path / "local")
    sync = PullSync(local, transport, interval=1)
    with pytest.raises(OSError):
        sync.pull()
    # "aaa" fetched fine *before* "bbb" died — it must still not be saved
    assert local.kernels() == []
    transport.heal()
    sync.pull()
    assert local.kernels() == ["aaa", "bbb"]


def test_tick_swallows_transport_failure_and_recovers(tmp_path):
    """The serving-loop hook must never let a sync hiccup escape into
    the decode step: failures are counted, the previously pulled wisdom
    stays served, and the next due tick retries."""
    transport = FlakyTransport(fail_on="k")
    served = Wisdom("k", [rec(score=5.0, config={"block": 9})])
    local = WisdomStore(tmp_path / "local")
    local.save(served)
    transport.publish("k", Wisdom("k", [rec(score=1.0,
                                            config={"block": 4})]).to_doc())
    sync = PullSync(local, transport, interval=2)
    assert sync.tick() is None                 # due, but transport raised
    assert sync.failures == 1 and isinstance(sync.last_error, OSError)
    assert local.load("k").records[0].config == {"block": 9}   # intact
    assert sync.tick() is None                 # off-interval: no attempt
    assert sync.failures == 1
    transport.heal()
    assert sync.tick() is not None             # due again: pull succeeds
    assert local.load("k").records[0].config == {"block": 4}


@pytest.mark.parametrize("transport_fails", [False, True],
                         ids=["pull", "transport-fails-mid-pull"])
def test_pull_sync_between_runs_reaches_the_next_trace(tmp_path,
                                                       transport_fails):
    """The engine traces its decode step once, so fleet wisdom is pulled
    between ``run()`` calls: a pull that lands refreshes the attached
    kernel for the next trace, a transport that dies mid-pull leaves the
    local store byte-identical, and either way the next run serves."""
    import jax.numpy as jnp
    from repro.core import WisdomKernel, get_kernel, load_builtin_kernels
    from repro.serve.engine import Request, ServeEngine

    load_builtin_kernels()
    problem = (8, 8, 8)
    default = get_kernel("matmul").default_config()
    local = WisdomStore(tmp_path / "local")
    local.save(Wisdom("matmul", [rec(problem=problem, score=100.0,
                                     config=dict(default, block_m=128))]))
    kernel = WisdomKernel(get_kernel("matmul"), wisdom_dir=local.root,
                          device_kind="tpu-v5e", backend="reference")

    class TinyLM:
        def init_cache(self, n_slots, max_seq):
            return {"pos": jnp.zeros((), jnp.int32)}

        def decode_step(self, params, cache, tok):
            h = kernel(params["w"], params["w"])       # a traced launch
            return jnp.broadcast_to(h[:1], (tok.shape[0], 1, 8)), cache

    transport = FlakyTransport(fail_on="matmul")
    if not transport_fails:
        transport.heal()
    transport.publish("aaa", Wisdom("aaa", [rec(config={"block": 2})])
                      .to_doc())
    transport.publish("matmul", Wisdom("matmul", [rec(
        problem=problem, score=10.0,
        config=dict(default, block_m=256))]).to_doc())
    sync = PullSync(local, transport, kernels=[kernel])

    eng = ServeEngine(TinyLM(), params={"w": jnp.ones((8, 8), jnp.float32)},
                      n_slots=1, max_seq=16)
    assert eng.submit(Request(0, np.array([1, 2], np.int32),
                              max_new_tokens=3))
    assert len(eng.run()[0]) == 3
    assert kernel.tier_counts == {"exact": 1}          # traced once
    before = {f.name: f.read_bytes() for f in local.root.iterdir()}

    pulled = sync.tick()                               # between runs
    cfg, tier = kernel.select_config(problem, "float32")
    if transport_fails:
        assert pulled is None and sync.failures == 1
        assert {f.name: f.read_bytes()
                for f in local.root.iterdir()} == before
        assert cfg["block_m"] == 128
    else:
        assert pulled is not None and sync.pulls == 1
        assert local.kernels() == ["aaa", "matmul"]
        assert (cfg["block_m"], tier) == (256, "exact")  # the next trace's

    assert eng.submit(Request(1, np.array([3], np.int32), max_new_tokens=2))
    out = eng.run()
    assert out.requests_completed == 1 and len(out[1]) == 2
    assert kernel.tier_counts == {"exact": 1}          # no retrace


def test_promotion_broadcast_hook(tmp_path, wisdom_dir):
    from repro.core import WisdomKernel, get_kernel, load_builtin_kernels
    from repro.online.promotion import PromotionPipeline

    load_builtin_kernels()
    kernel = WisdomKernel(get_kernel("matmul"), wisdom_dir=wisdom_dir,
                          device_kind="tpu-v5e", backend="reference")
    transport = MemoryTransport()
    push = PushSync(WisdomStore(wisdom_dir), transport)
    pipe = PromotionPipeline(kernel, wisdom_dir=wisdom_dir, broadcast=push)
    promo = pipe.promote(
        device_kind="tpu-v5e", problem=(64, 64, 64), dtype="float32",
        config=dict(kernel.builder.default_config()), score_us=10.0,
        incumbent_score_us=100.0, n_measurements=3, evals=12,
        objective="costmodel")
    assert promo is not None
    assert pipe.broadcasts == 1
    remote = transport.fetch("matmul")
    assert remote is not None and len(remote["records"]) == 1
    assert remote["records"][0]["score_us"] == 10.0


def test_tune_kernel_writes_through_store(tmp_path):
    from repro.core import get_kernel, load_builtin_kernels
    from repro.tuner.tune import tune_kernel

    load_builtin_kernels()
    store = WisdomStore(tmp_path / "w")
    res = tune_kernel(get_kernel("matmul"), (64, 64, 64), "float32",
                      "tpu-v5e", strategy="random", max_evals=4,
                      time_budget_s=None, store=store)
    assert res.best_config is not None
    wisdom = store.load("matmul")
    assert len(wisdom) == 1
    assert store.version_of("matmul") == WISDOM_VERSION


# ------------------------------------ CLI ------------------------------------

def test_cli_merge_matches_library(tmp_path, capsys):
    """Acceptance: `python -m repro.wisdom merge` produces the identical
    result to the library merge."""
    slow = rec(score=100.0, config={"block": 1}, host="hostA")
    fast = rec(score=40.0, config={"block": 8}, host="hostB")
    lib_a = store_with(tmp_path / "lib_a", slow)
    lib_b = store_with(tmp_path / "lib_b", fast)
    cli_a = store_with(tmp_path / "cli_a", slow)
    cli_b = store_with(tmp_path / "cli_b", fast)

    merge_stores(lib_a, lib_b)
    assert wisdom_cli(["merge", "--into", str(cli_a.root),
                       str(cli_b.root)]) == 0
    lib_doc = lib_a.path_for("k").read_text()
    cli_doc = cli_a.path_for("k").read_text()
    assert lib_doc == cli_doc                  # byte-identical on disk
    winner = cli_a.load("k").records[0]
    assert winner.config == {"block": 8}
    assert {e.get("host") for e in winner.lineage} == {"hostA", "hostB"}


def test_cli_inspect_validate_migrate_prune_diff(tmp_path, capsys):
    store = store_with(tmp_path / "s", rec(score=7.0, config={"block": 2}))
    assert wisdom_cli(["inspect", "--dir", str(store.root), "-v"]) == 0
    out = capsys.readouterr().out
    assert "k: 1 record(s)" in out and "7.00us" in out

    assert wisdom_cli(["validate", "--dir", str(store.root)]) == 0

    # v1 file -> validate ok, migrate rewrites it
    v1 = rec().to_json()
    del v1["lineage"]
    write_doc(store.path_for("old"), {"kernel": "old", "version": 1,
                                      "records": [v1]})
    assert wisdom_cli(["migrate", "--dir", str(store.root)]) == 0
    assert "old: migrated" in capsys.readouterr().out
    assert store.version_of("old") == WISDOM_VERSION

    # future version -> validate exits non-zero; diff/merge report the
    # version skew cleanly (exit 2) instead of crashing
    write_doc(store.path_for("future"),
              {"kernel": "future", "version": WISDOM_VERSION + 9,
               "records": []})
    assert wisdom_cli(["validate", "--dir", str(store.root)]) == 1
    capsys.readouterr()
    assert wisdom_cli(["diff", str(store.root), str(store.root)]) == 2
    assert "error:" in capsys.readouterr().out
    assert wisdom_cli(["merge", "--into", str(tmp_path / "m"),
                       str(store.root)]) == 2
    store.path_for("future").unlink()

    other = store_with(tmp_path / "o", rec(score=3.0, config={"block": 4}))
    assert wisdom_cli(["diff", str(store.root), str(other.root)]) == 1
    assert "conflict" in capsys.readouterr().out

    assert wisdom_cli(["prune", "--dir", str(store.root),
                       "--device", "tpu-v5e"]) == 0
