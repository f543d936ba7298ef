"""Serving engine: batched decode over a slot arena — token-level
continuous batching by default, lock-step cohorts as the fallback.

Two scheduling modes over the same static (n_slots, max_seq) KV arena
(see docs/serving.md for the slot lifecycle):

* **token** (default whenever the model's ``decode_supports_start`` says
  per-slot attention windows work — GQA and MLA stacks with rotary or no
  positions): the arena keeps one physical write
  cursor (``cache["pos"]``) but each slot owns a logical window
  ``[start[b], pos]`` carried in ``cache["start"]``. A request that
  finishes frees its slot *mid-stream*; the head of the batcher's queue
  is admitted at the current cursor and fed its prompt per-slot while
  other slots keep generating. When the arena runs out, the engine
  opens a fresh arena generation (new cache) and continues.
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
    """One generation request: prompt tokens in, sampled tokens out."""
    request_id: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int = 16
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
    mode: str = "cohort"
    occupancy: float = 0.0
    inflight_admissions: int = 0

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
                "steps": self.steps, "mode": self.mode,
                "occupancy": self.occupancy,
                "inflight_admissions": self.inflight_admissions}


class ServeEngine:
    """Continuous-batching LM server over a static KV arena.

    Submit :class:`Request` objects, then :meth:`run` to completion; the
    returned :class:`ServeReport` maps request ids to generated tokens
    plus run statistics. ``mode`` is ``"auto"`` (token-level when the
    model supports per-slot attention windows, else cohort), ``"token"``
    or ``"cohort"``. One optional collaborator, ``profiler``, is the step
    clock: it is bound to the engine and asked once per decode step, in
    either mode, whether to time that step (``bind``/``due``/``on_step``,
    as ``repro.prof.StepProfiler`` and the benchmark's step clock
    implement them).

    The decode step is traced once per engine, so a change to wisdom
    never reaches it: callers tick online tuners (``OnlineTuner.tick``)
    and fleet sync (``PullSync.tick``) themselves, between :meth:`run`
    calls, for the next engine's trace.

    Example::

        eng = ServeEngine(model, params, n_slots=4, max_seq=256)
        eng.submit(Request(0, np.array([1, 2, 3]), max_new_tokens=8))
        report = eng.run()
        report[0]          # -> 8 generated token ids
    """

    def __init__(self, model, params, n_slots: int = 4,
                 max_seq: int = 512, temperature: float = 0.0,
                 rng_seed: int = 0, profiler=None, mode: str = "auto"):
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
        # Optional decode-step profiler (repro.prof.StepProfiler): every
        # Nth step is timed to a blocking boundary and recorded as a
        # "serve.decode" roofline profile (params streamed from HBM per
        # step → small-batch decode is memory-bound; the profile says by
        # how much, and drifts against the run's first sampled step).
        # Unsampled steps pay one None check — no extra block/clock.
        self.profiler = profiler
        if profiler is not None:
            profiler.bind(params, n_slots, max_seq)

    def submit(self, req: Request) -> bool:
        ok = self.batcher.submit(req.request_id, len(req.prompt),
                                 req.max_new_tokens)
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

    def _count_step(self, m) -> None:
        """The decode-step counter (both modes), under the ``serve.tick``
        span that the benchmark's span list still names."""
        with obs.span("serve.tick", cat="serve"):
            if m is not None:
                m.counter("serve.decode_steps").inc()

    # -- cohort mode ---------------------------------------------------------

    def _run_cohort(self, members: list[tuple[int, int, int]]) -> None:
        """members: [(slot, request_id, prompt_len)]. Fresh cache; decode
        in lock-step until the batcher has freed every member's slot."""
        b = self.batcher
        cache = self._new_cache()
        reqs = {slot: self._requests[rid] for slot, rid, _ in members}
        next_tok = np.zeros((self.n_slots, 1), np.int32)
        for slot, req in reqs.items():
            next_tok[slot, 0] = req.prompt[0]
        for t in range(self.max_seq - 1):
            live = [slot for slot in reqs if b.slots[slot].active]
            if not live:
                break
            with obs.span("serve.step", cat="serve"):
                m = obs.metrics()
                self._useful_slot_steps += len(live)
                if m is not None:
                    m.histogram("batch.occupancy",
                                UNIT_BUCKETS).observe(len(live)
                                                      / self.n_slots)
                logits, cache = self._decode_once(cache, next_tok)
                self._count_step(m)
                sampled = self._sample_logits(logits)
                with obs.span("serve.advance", cat="serve"):
                    for slot in live:
                        req = reqs[slot]
                        if t + 1 < len(req.prompt):
                            next_tok[slot, 0] = req.prompt[t + 1]  # feeding
                            continue
                        req.tokens.append(int(sampled[slot]))
                        next_tok[slot, 0] = sampled[slot]
                        b.advance(slot)
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
                self._count_step(m)
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
            mode=self.mode,
            occupancy=(round(useful / (steps * self.n_slots), 4)
                       if steps else 0.0),
            inflight_admissions=self._inflight_admissions - inflight0)
