"""Token-level vs cohort serving throughput on a mixed-length workload.

The number this PR exists to move: on a workload whose requests have
*unequal* lengths, lock-step cohorts stall every slot on the cohort's
slowest member, while token-level continuous batching refills a freed
slot mid-stream (per-slot attention-window masking over the shared
arena, see docs/serving.md). Both modes run the identical workload on
the identical tiny transformer with greedy sampling, so the comparison
is purely scheduling.

Gates (``--check``, part of the ``serve-smoke`` CI job):

* token-level completes the workload in strictly fewer decode steps;
* token-level's slot occupancy (useful slot-steps / total slot-steps)
  is strictly higher;
* both modes return identical per-request token counts (scheduling must
  not change how much gets generated).

CSV: mode, requests, steps, arena_generations, occupancy,
inflight_admissions, tokens_per_step.
"""

from __future__ import annotations

import sys

import numpy as np

try:
    from .common import csv_row
except ImportError:     # run as a plain script: python benchmarks/...py
    def csv_row(*fields) -> str:
        return ",".join(str(f) for f in fields)

N_SLOTS = 4
MAX_SEQ = 96

#: (prompt_len, max_new_tokens) per request — deliberately mixed lengths
#: (short chats next to long generations) so cohort mode pays its
#: slowest-member stall on every cohort.
WORKLOAD = ((4, 4), (6, 40), (3, 6), (5, 28), (4, 8), (8, 36),
            (2, 4), (6, 24), (3, 10), (5, 32), (4, 6), (7, 20))


def _model():
    from repro.configs import get_arch
    from repro.models import build_model
    cfg = get_arch("stablelm-1.6b").reduced()
    return cfg, build_model(cfg)


def serve_mode(mode: str):
    """Run the workload under one scheduling mode; returns the report."""
    import jax
    from repro.serve import Request, ServeEngine
    cfg, model = _model()
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                      mode=mode)
    rng = np.random.default_rng(7)
    for rid, (plen, mnew) in enumerate(WORKLOAD):
        eng.submit(Request(
            rid, rng.integers(0, cfg.vocab, size=plen, dtype=np.int32),
            max_new_tokens=mnew))
    return eng.run()


def run():
    yield csv_row("serve_throughput", "mode", "requests", "steps",
                  "arena_generations", "occupancy",
                  "inflight_admissions", "tokens_per_step")
    reports = {mode: serve_mode(mode) for mode in ("token", "cohort")}
    for mode, rep in reports.items():
        tokens = sum(len(t) for t in rep.values())
        yield csv_row("serve_throughput", mode, rep.requests_completed,
                      rep.steps, rep.cohorts, f"{rep.occupancy:.4f}",
                      rep.inflight_admissions,
                      f"{tokens / rep.steps:.4f}" if rep.steps else "0")
    token, cohort = reports["token"], reports["cohort"]
    same_outputs = ({rid: len(t) for rid, t in token.items()}
                    == {rid: len(t) for rid, t in cohort.items()})
    run.passed = (token.steps < cohort.steps
                  and token.occupancy > cohort.occupancy
                  and same_outputs)
    yield csv_row("serve_throughput_gate", "token_beats_cohort")
    yield csv_row("serve_throughput_gate", int(run.passed))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    check = "--check" in argv
    for row in run():
        print(row)
    if check and not run.passed:
        print("serve_throughput: FAILED (token-level did not beat cohort "
              "on steps+occupancy with identical outputs)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
