"""Serving engine: batched decode over a slot arena — token-level
continuous batching by default, lock-step cohorts as the fallback.

Two scheduling modes over the same static (n_slots, max_seq) KV arena
(see docs/serving.md for the slot lifecycle):

* **token** (default whenever the model's ``decode_supports_start`` says
  per-slot attention windows work — GQA and MLA stacks with rotary or no
  positions): the arena keeps one physical write
  cursor (``cache["pos"]``) but each slot owns a logical window
  ``[start[b], pos]`` carried in ``cache["start"]``. A request that
  finishes frees its slot *mid-stream*; the next queued request — picked
  from the batcher's scenario buckets so concurrent slots share a tuned
  scenario — is admitted at the current cursor and fed its prompt
  per-slot while other slots keep generating. When the arena runs out,
  the engine opens a fresh arena generation (new cache) and continues.
  Stale K/V from a slot's previous occupant sits below ``start`` and is
  masked out of attention entirely (zeroing would still leak softmax
  weight), which also keeps rotary phases correct: only relative
  distances within a slot's own window survive the mask.

* **cohort** (fallback for recurrent mixers, cross-attention and
  learned-position models — their decode state cannot be scoped to a
  slot window by masking — and the A/B baseline for benchmarks): admit a
  cohort into free slots and run lock-step until every member finishes;
  every cohort stalls on its slowest member, which is exactly the
  occupancy loss ``benchmarks/serve_throughput.py`` measures.

Greedy (argmax) or temperature sampling.

Device tallies: a model that lists ``cache_tallies`` keeps scalar
counters in its decode cache, updated on the device every step (the
held experts' routed (token, k) pairs); :meth:`ServeEngine.tallies`
pulls them, summed over every arena the engine has run, and no step
waits for them.

With tracing on (``repro.obs``) every decode step is one ``serve.step``
span whose children are its phases: ``serve.admit`` (token mode),
``serve.dispatch``, ``serve.wait`` (steps the profiler samples),
``serve.tick``, ``serve.sample`` and ``serve.advance``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import runtime as obs
from repro.obs.metrics import COUNT_BUCKETS, UNIT_BUCKETS

from .batching import ContinuousBatcher


@dataclass
class Request:
    """One generation request: prompt tokens in, sampled tokens out.

    ``scenario`` is an optional tuned-scenario key (``core/scenario.py``
    ``format_key`` string, e.g. ``"tpu-v5e|256x256|float32"``): the
    batcher buckets admission by it so slots running concurrently share
    a wisdom-exact configuration. Empty string = unbucketed."""
    request_id: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int = 16
    scenario: str = ""
    tokens: list = field(default_factory=list)   # generated


@dataclass
class ServeReport:
    """What one :meth:`ServeEngine.run` call did.

    Mapping-compatible with the historical ``{request_id: tokens}``
    return value (``report[rid]``, iteration, ``len``, ``in`` all
    delegate to :attr:`outputs`), so existing callers keep working while
    new ones read the run stats directly. ``cohorts`` counts lock-step
    cohorts in cohort mode and arena generations in token mode;
    ``occupancy`` is the fraction of slot-steps that advanced a live
    request (the number token-level scheduling exists to raise);
    ``inflight_admissions`` counts requests admitted while other slots
    were mid-generation — always 0 in cohort mode.
    """

    outputs: dict[int, list[int]]
    cohorts: int = 0
    requests_completed: int = 0
    steps: int = 0
    sync_pulls: int = 0
    sync_failures: int = 0
    mode: str = "cohort"
    occupancy: float = 0.0
    inflight_admissions: int = 0
    scenario_switches: int = 0

    def __getitem__(self, request_id: int) -> list[int]:
        return self.outputs[request_id]

    def __iter__(self):
        return iter(self.outputs)

    def __len__(self) -> int:
        return len(self.outputs)

    def __contains__(self, request_id: int) -> bool:
        return request_id in self.outputs

    def keys(self):
        return self.outputs.keys()

    def values(self):
        return self.outputs.values()

    def items(self):
        return self.outputs.items()

    def to_json(self) -> dict:
        return {"cohorts": self.cohorts,
                "requests_completed": self.requests_completed,
                "steps": self.steps, "sync_pulls": self.sync_pulls,
                "sync_failures": self.sync_failures, "mode": self.mode,
                "occupancy": self.occupancy,
                "inflight_admissions": self.inflight_admissions,
                "scenario_switches": self.scenario_switches}


class ServeEngine:
    """Continuous-batching LM server over a static KV arena.

    Submit :class:`Request` objects, then :meth:`run` to completion; the
    returned :class:`ServeReport` maps request ids to generated tokens
    plus run statistics. ``mode`` is ``"auto"`` (token-level when the
    model supports per-slot attention windows, else cohort), ``"token"``
    or ``"cohort"``. Optional collaborators: online autotuners
    (``repro.online``), fleet wisdom sync (``repro.distrib.PullSync``)
    and a decode-step roofline profiler (``repro.prof``) all tick once
    per decode step in either mode.

    Example::

        eng = ServeEngine(model, params, n_slots=4, max_seq=256)
        eng.submit(Request(0, np.array([1, 2, 3]), max_new_tokens=8))
        report = eng.run()
        report[0]          # -> 8 generated token ids
    """

    def __init__(self, model, params, n_slots: int = 4,
                 max_seq: int = 512, temperature: float = 0.0,
                 rng_seed: int = 0, online=None, sync=None,
                 profiler=None, mode: str = "auto"):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.temperature = temperature
        if mode not in ("auto", "token", "cohort"):
            raise ValueError(f"unknown serve mode {mode!r} "
                             f"(want auto|token|cohort)")
        if mode == "auto":
            mode = ("token"
                    if getattr(model, "decode_supports_start", False)
                    else "cohort")
        self.mode = mode
        self.batcher = ContinuousBatcher(n_slots, max_seq)
        # The cache is donated: the step writes the arena in place, and
        # every caller rebinds the cache to the step's result.
        self._decode = jax.jit(model.decode_step, donate_argnums=(1,))
        #: The live decode cache (the last step's), kept so that its
        #: device tallies can be read; ``_tally_base`` holds those of
        #: arenas already retired.
        self.cache = None
        #: arenas opened so far (generations and cohorts, both modes)
        self.arenas = 0
        self._tally_names = tuple(getattr(model, "cache_tallies", ()))
        self._tally_base = dict.fromkeys(self._tally_names, 0)
        self._requests: dict[int, Request] = {}
        self._rng = np.random.default_rng(rng_seed)
        self.steps_run = 0
        self._useful_slot_steps = 0
        self._inflight_admissions = 0
        # Optional online autotuner(s) (repro.online.OnlineTuner): each
        # decode step sponsors one launch-budget slice of background tuning
        # via tick(). Kernels launched inside the jitted decode report
        # their scenario at trace time (observe_traced); tick() screens
        # them and — under the cost-model objective — resolves their
        # bracket too, since live trials can't be interleaved into a
        # compiled graph. Promotions land in wisdom for the next trace.
        if online is None:
            online = []
        elif not isinstance(online, (list, tuple)):
            online = [online]
        self.online = list(online)
        # Optional fleet wisdom pull (repro.distrib.PullSync): tick() is
        # called once per decode step and actually pulls every
        # sync.interval ticks, merging fleet wisdom into the local store
        # and hot-refreshing attached kernels — this host serves with the
        # whole fleet's tuning results, not just its own.
        self.sync = sync
        # Optional decode-step profiler (repro.prof.StepProfiler): every
        # Nth step is timed to a blocking boundary and recorded as a
        # "serve.decode" roofline profile (params streamed from HBM per
        # step → small-batch decode is memory-bound; the profile says by
        # how much, and drifts against the run's first sampled step).
        # Unsampled steps pay one None check — no extra block/clock.
        self.profiler = profiler
        if profiler is None:
            from repro.prof.profiler import (StepProfiler,
                                             process_profiler)
            ambient = process_profiler()
            if ambient is not None:
                self.profiler = StepProfiler(ambient)
        if self.profiler is not None:
            self.profiler.bind(params, n_slots, max_seq)

    def submit(self, req: Request) -> bool:
        ok = self.batcher.submit(req.request_id, len(req.prompt),
                                 req.max_new_tokens,
                                 scenario=req.scenario)
        if ok:
            self._requests[req.request_id] = req
        return ok

    def _sample(self, logits: np.ndarray) -> np.ndarray:
        if self.temperature <= 0:
            return logits.argmax(-1).astype(np.int32)
        z = logits / self.temperature
        z = z - z.max(-1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(-1, keepdims=True)
        return np.array([self._rng.choice(p.shape[-1], p=pi)
                         for pi in p], np.int32)

    def _decode_once(self, cache, next_tok, starts=None):
        """One jitted decode step: the host uploads (``starts`` becomes
        ``cache["start"]``) and the call under ``serve.dispatch``, which
        returns before the device finishes. A step the profiler samples
        also waits for the logits (``serve.wait``) and reports its time
        to the profiler."""
        prof = self.profiler
        sampled = prof is not None and prof.due(self.steps_run)
        t0 = time.perf_counter()
        with obs.span("serve.dispatch", cat="serve"):
            if starts is not None:
                cache["start"] = jnp.asarray(starts)
            logits, cache = self._decode(self.params, cache,
                                         jnp.asarray(next_tok))
            self.cache = cache
        if sampled:
            # Only sampled steps pay the extra sync; the rest overlap
            # the device's work with the host's until the logits pull.
            with obs.span("serve.wait", cat="serve"):
                logits = jax.block_until_ready(logits)
            prof.on_step((time.perf_counter() - t0) * 1e6)
        self.steps_run += 1
        return logits, cache

    def _sample_logits(self, logits) -> np.ndarray:
        """Pull the last position's logits to the host (waiting for the
        device if no sampled step waited already) and sample."""
        with obs.span("serve.sample", cat="serve"):
            return self._sample(np.asarray(logits[:, 0]))

    def _new_cache(self):
        """A fresh arena; the retired one's tallies join the base."""
        if self.cache is not None:
            for name in self._tally_names:
                self._tally_base[name] += int(self.cache[name])
            self.cache = None       # free the old arena before the new one
        self.cache = self.model.init_cache(self.n_slots, self.max_seq)
        self.arenas += 1
        return self.cache

    def tallies(self) -> dict[str, int]:
        """The model's device tallies (``cache_tallies``) summed over every
        arena this engine has run; reads the live cache from the device."""
        out = dict(self._tally_base)
        if self.cache is not None:
            for name in self._tally_names:
                out[name] += int(self.cache[name])
        return out

    def _tick_services(self, m) -> None:
        """Per-decode-step collaborator ticks (both modes)."""
        with obs.span("serve.tick", cat="serve"):
            if m is not None:
                m.counter("serve.decode_steps").inc()
            for svc in self.online:
                svc.tick()
            if self.sync is not None:
                fails_before = self.sync.failures
                pulled = self.sync.tick()
                if m is not None:
                    if pulled is not None:
                        outcome = "pulled"
                    elif self.sync.failures > fails_before:
                        outcome = "failed"
                    else:
                        outcome = "skipped"
                    m.counter("serve.sync_tick", outcome=outcome).inc()

    # -- cohort mode ---------------------------------------------------------

    def _run_cohort(self, members: list[tuple[int, int, int]]) -> None:
        """members: [(slot, request_id, prompt_len)]. Fresh cache; decode
        in lock-step until every member has its tokens."""
        cache = self._new_cache()
        reqs = {slot: self._requests[rid] for slot, rid, _ in members}
        done = {slot: False for slot in reqs}
        next_tok = np.zeros((self.n_slots, 1), np.int32)
        for slot, req in reqs.items():
            next_tok[slot, 0] = req.prompt[0]
        t = 0
        while not all(done.values()) and t < self.max_seq - 1:
            with obs.span("serve.step", cat="serve"):
                m = obs.metrics()
                live = sum(1 for v in done.values() if not v)
                self._useful_slot_steps += live
                if m is not None:
                    m.histogram("batch.occupancy",
                                UNIT_BUCKETS).observe(live / self.n_slots)
                logits, cache = self._decode_once(cache, next_tok)
                self._tick_services(m)
                sampled = self._sample_logits(logits)
                with obs.span("serve.advance", cat="serve"):
                    for slot, req in reqs.items():
                        if done[slot]:
                            continue
                        if t + 1 < len(req.prompt):
                            next_tok[slot, 0] = req.prompt[t + 1]  # feeding
                        else:
                            req.tokens.append(int(sampled[slot]))
                            next_tok[slot, 0] = sampled[slot]
                            if len(req.tokens) >= req.max_new_tokens:
                                done[slot] = True
            t += 1
        # release slots
        for slot, rid, _ in members:
            s = self.batcher.slots[slot]
            self.batcher.finished.append(rid)
            s.active = False
            s.request_id = None
        m = obs.metrics()
        if m is not None:
            m.counter("serve.requests_completed").inc(len(members))

    def _run_cohort_mode(self, max_cohorts: int) -> int:
        cohorts = 0
        for _ in range(max_cohorts):
            if self.batcher.done():
                break
            members = self.batcher.admit()
            if not members:
                continue
            m = obs.metrics()
            if m is not None:
                m.histogram("serve.cohort_size",
                            COUNT_BUCKETS).observe(len(members))
                m.gauge("serve.queue_depth").set(self.batcher.queue_depth)
            with obs.span("serve.cohort", cat="serve", cohort=cohorts,
                          size=len(members)):
                self._run_cohort(members)
            cohorts += 1
        return cohorts

    # -- token mode ----------------------------------------------------------

    def _admit(self, arena_pos: int, starts: np.ndarray, fed: list[int],
               next_tok: np.ndarray) -> int:
        """Admit queued requests into free slots at the arena cursor and
        queue each one's first prompt token; returns how many."""
        b = self.batcher
        with obs.span("serve.admit", cat="serve") as span:
            active_before = b.active_slots
            admitted = b.admit(arena_pos=arena_pos)
            for slot, rid, _plen in admitted:
                next_tok[slot, 0] = self._requests[rid].prompt[0]
                starts[slot] = arena_pos
                fed[slot] = 1
            if admitted:
                if active_before > 0:
                    self._inflight_admissions += len(admitted)
                m = obs.metrics()
                if m is not None:
                    m.gauge("serve.queue_depth").set(b.queue_depth)
            if span is not None:
                span["requests"] = [rid for _, rid, _ in admitted]
        return len(admitted)

    def _run_arena(self) -> None:
        """One arena generation: fresh cache, write cursor at 0, then
        token-level decode — freed slots admit queued requests mid-stream
        at the current cursor — until the queue and slots drain or the
        remaining arena cannot hold the next (head-of-line) request."""
        b = self.batcher
        cache = self._new_cache()
        starts = np.zeros(self.n_slots, np.int32)
        fed = [0] * self.n_slots           # prompt tokens fed per slot
        next_tok = np.zeros((self.n_slots, 1), np.int32)
        arena_pos = 0
        while arena_pos < self.max_seq:
            # With every slot free, only an admission makes a step: it
            # runs before the step, which then exists only if it admitted.
            idle = not b.active_slots
            if idle and not self._admit(arena_pos, starts, fed, next_tok):
                break       # drained, or head request needs a fresh arena
            with obs.span("serve.step", cat="serve"):
                if not idle:
                    self._admit(arena_pos, starts, fed, next_tok)
                m = obs.metrics()
                active = [i for i, s in enumerate(b.slots) if s.active]
                self._useful_slot_steps += len(active)
                if m is not None:
                    m.histogram("batch.occupancy",
                                UNIT_BUCKETS).observe(len(active)
                                                      / self.n_slots)
                logits, cache = self._decode_once(cache, next_tok, starts)
                arena_pos += 1
                self._tick_services(m)
                sampled = self._sample_logits(logits)
                with obs.span("serve.advance", cat="serve"):
                    completed = 0
                    for i in active:
                        req = self._requests[b.slots[i].request_id]
                        if fed[i] < len(req.prompt):
                            next_tok[i, 0] = req.prompt[fed[i]]  # feeding
                            fed[i] += 1
                            continue
                        req.tokens.append(int(sampled[i]))
                        next_tok[i, 0] = sampled[i]
                        if b.advance(i) is not None:
                            completed += 1  # slot freed; refilled next step
                    if completed and m is not None:
                        m.counter("serve.requests_completed").inc(completed)

    def _run_token_mode(self, max_generations: int) -> int:
        generations = 0
        while generations < max_generations and not self.batcher.done():
            with obs.span("serve.arena", cat="serve",
                          generation=generations):
                self._run_arena()
            generations += 1
        return generations

    # -- driver --------------------------------------------------------------

    def run(self, max_cohorts: int = 1000) -> ServeReport:
        """Serve every submitted request to completion. ``max_cohorts``
        bounds lock-step cohorts (cohort mode) or arena generations
        (token mode) as a runaway backstop."""
        steps0 = self.steps_run
        done0 = len(self.batcher.finished)
        useful0 = self._useful_slot_steps
        inflight0 = self._inflight_admissions
        switches0 = self.batcher.scenario_switches
        pulls0 = self.sync.pulls if self.sync is not None else 0
        fails0 = self.sync.failures if self.sync is not None else 0
        if self.mode == "token":
            cohorts = self._run_token_mode(max_cohorts)
        else:
            cohorts = self._run_cohort_mode(max_cohorts)
        steps = self.steps_run - steps0
        useful = self._useful_slot_steps - useful0
        return ServeReport(
            outputs={rid: r.tokens for rid, r in self._requests.items()},
            cohorts=cohorts,
            requests_completed=len(self.batcher.finished) - done0,
            steps=steps,
            sync_pulls=(self.sync.pulls - pulls0
                        if self.sync is not None else 0),
            sync_failures=(self.sync.failures - fails0
                           if self.sync is not None else 0),
            mode=self.mode,
            occupancy=(round(useful / (steps * self.n_slots), 4)
                       if steps else 0.0),
            inflight_admissions=self._inflight_admissions - inflight0,
            scenario_switches=(self.batcher.scenario_switches
                               - switches0))
