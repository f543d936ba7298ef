"""Continuous-batcher scheduling properties + CLI entry points."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serve.batching import ContinuousBatcher


def test_admission_and_slot_lifecycle():
    b = ContinuousBatcher(n_slots=2, max_seq=64)
    for rid in range(3):
        assert b.submit(rid, prompt_len=4, max_new_tokens=4)
    admitted = b.admit()
    assert [a[0] for a in admitted] == [0, 1]       # two slots filled
    assert b.active_slots == 2
    assert b.admit() == []                           # queue waits
    for _ in range(4):
        b.step()
    assert b.active_slots == 0
    assert sorted(b.finished) == [0, 1]
    admitted = b.admit()                             # third request enters
    assert admitted[0][1] == 2
    assert not b.done()


def test_rejection_of_oversize():
    b = ContinuousBatcher(n_slots=1, max_seq=16)
    assert not b.submit(9, prompt_len=10, max_new_tokens=10)
    assert b.rejected == [9]
    assert b.done()


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_every_accepted_request_eventually_finishes(data):
    """Property: any mix of valid requests drains completely."""
    n_slots = data.draw(st.integers(1, 4))
    b = ContinuousBatcher(n_slots=n_slots, max_seq=32)
    n_req = data.draw(st.integers(1, 10))
    accepted = []
    for rid in range(n_req):
        plen = data.draw(st.integers(1, 20))
        mnew = data.draw(st.integers(1, 20))
        if b.submit(rid, plen, mnew):
            accepted.append(rid)
    for _ in range(10_000):
        if b.done():
            break
        b.admit()
        b.step()
    assert b.done()
    assert sorted(b.finished) == sorted(accepted)


def test_head_of_line_capacity_blocking():
    """A head request that does not fit the remaining arena blocks the
    queue — later, smaller requests must not skip past it (skipping
    would starve long requests)."""
    b = ContinuousBatcher(n_slots=2, max_seq=32)
    b.submit(0, 16, 12)                 # needs 28 columns
    b.submit(1, 2, 2)                   # would fit anywhere
    assert b.admit(arena_pos=8) == []   # 8 + 28 > 32: head blocks queue
    admitted = [rid for _, rid, _ in b.admit(arena_pos=0)]
    assert admitted == [0, 1]           # fresh arena: FIFO order intact


@settings(max_examples=60, deadline=None)
@given(st.data())
def test_randomized_interleaving_conserves_requests(data):
    """Stress property: under any seeded interleaving of submit / admit /
    advance, every accepted request lives in exactly one of
    {queue, slot, finished}, no request is lost or duplicated, rejected
    requests never reach a slot, and admission order equals submission
    order."""
    n_slots = data.draw(st.integers(1, 4))
    max_seq = data.draw(st.sampled_from([16, 32]))
    b = ContinuousBatcher(n_slots=n_slots, max_seq=max_seq)
    accepted, rejected, admitted_order = set(), set(), []
    submitted_order = []
    next_rid = 0

    def check_invariants():
        queued = {q.request_id for q in b.queue}
        in_slots = {s.request_id for s in b.slots if s.active}
        finished = set(b.finished)
        assert len(b.finished) == len(finished)          # no duplicates
        assert queued | in_slots | finished == accepted  # none lost
        assert not (queued & in_slots) and not (queued & finished)
        assert not (in_slots & finished)                 # exactly one place
        assert not (rejected & (queued | in_slots | finished))
        assert sum(s.active for s in b.slots) + sum(
            not s.active for s in b.slots) == n_slots    # slots conserved
        assert [q.request_id for q in b.queue] == \
            submitted_order[len(admitted_order):]        # queue is FIFO

    for _ in range(data.draw(st.integers(5, 40))):
        op = data.draw(st.sampled_from(["submit", "admit", "advance"]))
        if op == "submit":
            plen = data.draw(st.integers(1, 20))
            mnew = data.draw(st.integers(1, 20))
            if b.submit(next_rid, plen, mnew):
                accepted.add(next_rid)
                submitted_order.append(next_rid)
            else:
                rejected.add(next_rid)
            next_rid += 1
        elif op == "admit":
            pos = data.draw(st.integers(0, max_seq - 1))
            for _slot, rid, _plen in b.admit(arena_pos=pos):
                admitted_order.append(rid)
        else:
            active = [i for i, s in enumerate(b.slots) if s.active]
            if active:
                b.advance(data.draw(st.sampled_from(active)))
        check_invariants()

    # One FIFO: the admitted rids are a prefix of submission order.
    assert admitted_order == submitted_order[:len(admitted_order)]


class _StartAwareToyModel:
    """Decode-only toy with the token-mode contract: advertises
    ``decode_supports_start`` and tolerates ``cache["start"]``.
    Next token = (tok + 1) mod vocab, so outputs are deterministic."""

    vocab = 13
    decode_supports_start = True

    def init_cache(self, n_slots, max_seq):
        import jax.numpy as jnp
        return {"pos": jnp.zeros((), jnp.int32)}

    def decode_step(self, params, cache, tok):
        import jax
        import jax.numpy as jnp
        logits = jax.nn.one_hot((tok[:, 0] + 1) % self.vocab,
                                self.vocab)[:, None]
        return logits, {**cache, "pos": cache["pos"] + 1}


def test_mid_stream_admission_token_mode():
    """Token mode refills freed slots while other slots keep decoding:
    mixed-length traffic must report in-flight admissions, and every
    request still gets exactly ``max_new_tokens`` outputs."""
    from repro.serve import Request, ServeEngine
    eng = ServeEngine(_StartAwareToyModel(), params={}, n_slots=2,
                      max_seq=64)
    assert eng.mode == "token"          # auto picks token for this model
    lengths = {0: 2, 1: 9, 2: 3, 3: 5}  # short ones free mid-stream
    for rid, mnew in lengths.items():
        assert eng.submit(Request(rid, np.array([1, 2], np.int32),
                                  max_new_tokens=mnew))
    out = eng.run()
    assert out.mode == "token"
    assert eng.batcher.done()
    assert {rid: len(out[rid]) for rid in lengths} == lengths
    # greedy toy model: tokens continue the +1 sequence from prompt end
    assert out[0][:2] == [3, 4]
    # rids 2/3 were queued behind a still-running slot -> admitted
    # mid-stream, not at an arena boundary
    assert out.inflight_admissions >= 1
    assert 0.0 < out.occupancy <= 1.0
    assert out.cohorts == 1             # everything fits one arena


def test_cohort_mode_forced_on_token_capable_model():
    """mode="cohort" must override auto-detection — the fallback path
    stays reachable for A/B measurement (benchmarks/serve_throughput)."""
    from repro.serve import Request, ServeEngine
    eng = ServeEngine(_StartAwareToyModel(), params={}, n_slots=2,
                      max_seq=32, mode="cohort")
    assert eng.mode == "cohort"
    for rid in range(3):
        eng.submit(Request(rid, np.array([1], np.int32), max_new_tokens=2))
    out = eng.run()
    assert out.mode == "cohort"
    assert out.cohorts == 2 and out.inflight_admissions == 0
    assert {rid: len(out[rid]) for rid in range(3)} == {0: 2, 1: 2, 2: 2}


class _TraceCountingToyModel(_StartAwareToyModel):
    """Token-mode toy whose ``decode_step`` counts its Python traces."""

    def __init__(self):
        self.traces = 0

    def decode_step(self, params, cache, tok):
        self.traces += 1
        return super().decode_step(params, cache, tok)


def test_decode_step_traces_once_across_generations():
    """The engine's jitted step has one static shape, so a run that
    opens several arena generations and admits mid-stream still traces
    it once: nothing changed between steps (wisdom included) can reach
    the kernels selected in that trace."""
    from repro.serve import Request, ServeEngine
    model = _TraceCountingToyModel()
    eng = ServeEngine(model, params={}, n_slots=2, max_seq=16)
    for rid, mnew in enumerate([2, 9, 3, 5, 6, 4, 7]):
        assert eng.submit(Request(rid, np.array([1, 2], np.int32),
                                  max_new_tokens=mnew))
    out = eng.run()
    assert out.requests_completed == 7
    assert out.cohorts >= 2 and out.inflight_admissions >= 1
    assert model.traces == 1


class _PosToyModel:
    """Cohort-only toy (no ``decode_supports_start``): next token =
    (tok + arena position) mod vocab, so outputs pin each token to the
    step that made it."""

    vocab = 13

    def init_cache(self, n_slots, max_seq):
        import jax.numpy as jnp
        return {"pos": jnp.zeros((), jnp.int32)}

    def decode_step(self, params, cache, tok):
        import jax
        nxt = (tok[:, 0] + cache["pos"]) % self.vocab
        return (jax.nn.one_hot(nxt, self.vocab)[:, None],
                {"pos": cache["pos"] + 1})


def test_cohort_mode_frees_slots_through_the_batcher():
    """Cohort mode advances each generated token through the batcher,
    which alone frees a slot: afterwards every slot is free, each id is
    finished once (in completion order), and the outputs and step count
    are those of the lock-step loop that decided completion itself."""
    from repro.serve import Request, ServeEngine
    eng = ServeEngine(_PosToyModel(), params={}, n_slots=2, max_seq=16)
    assert eng.mode == "cohort"
    work = {0: ([1, 2, 3], 4), 1: ([5], 2), 2: ([7, 8], 5), 3: ([2], 1),
            4: ([4, 6, 9], 3)}
    for rid, (prompt, mnew) in work.items():
        assert eng.submit(Request(rid, np.array(prompt, np.int32),
                                  max_new_tokens=mnew))
    out = eng.run()
    assert not any(s.active for s in eng.batcher.slots)
    assert eng.batcher.finished == [1, 0, 3, 2, 4]
    assert dict(out.items()) == {0: [5, 8, 12, 4], 1: [5, 6],
                                 2: [9, 11, 1, 5, 10], 3: [2],
                                 4: [11, 1, 5]}
    assert (out.cohorts, out.steps, out.occupancy) == (3, 17, 0.5882)


def test_tuner_cli_end_to_end(tmp_path, monkeypatch, capture_dir,
                              wisdom_dir, small_fields):
    """python -m repro.tuner.tune over a real capture directory."""
    from repro.core import CAPTURE_ENV, WisdomKernel, get_kernel
    from repro.tuner.tune import main

    u, v, w, _, scal = small_fields
    monkeypatch.setenv(CAPTURE_ENV, "advec_u")
    WisdomKernel(get_kernel("advec_u"), wisdom_dir=wisdom_dir,
                 device_kind="tpu-v5e", backend="reference")(u, v, w, scal)
    monkeypatch.delenv(CAPTURE_ENV)
    rc = main(["--captures", f"{capture_dir}/*.capture.json",
               "--strategy", "anneal", "--budget-evals", "30",
               "--budget-seconds", "30", "--device", "tpu-v5e",
               "--wisdom-dir", str(wisdom_dir)])
    assert rc == 0
    from repro.core import Wisdom
    assert len(Wisdom.load("advec_u", wisdom_dir)) >= 1


def test_tuner_cli_no_captures(tmp_path):
    from repro.tuner.tune import main
    assert main(["--captures", f"{tmp_path}/none/*.json"]) == 1


def test_shipped_wisdom_is_loadable_and_selected():
    """The repo's pre-tuned wisdom/ files drive selection out of the box."""
    from pathlib import Path
    from repro.core import Wisdom, WisdomKernel, get_kernel
    wdir = Path(__file__).resolve().parents[1] / "wisdom"
    if not wdir.exists():
        pytest.skip("wisdom/ not generated")
    w = Wisdom.load("matmul", wdir)
    assert len(w) >= 4
    k = WisdomKernel(get_kernel("matmul"), wisdom_dir=wdir,
                     device_kind="tpu-v5e")
    cfg, tier = k.select_config((4096, 4096, 4096), "bfloat16")
    assert tier == "exact"
    cfg2, tier2 = k.select_config((5000, 5000, 5000), "bfloat16")
    assert tier2 == "device+dtype"        # fuzzy match on the shipped data
