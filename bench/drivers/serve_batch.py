"""Driver of the serving cells: ``ServeEngine.run`` on a decoder LM at its
published widths, fed a constant backlog of requests.

The window is one ``run()`` call. The harness hands the engine its own
step clock as the engine's ``profiler`` collaborator, due on every decode
step; the engine calls it once the step's logits are on the host. On
each step it records the time and the slots in use, and tops the backlog
back up. At the first step after ``--seconds`` the window closes: tokens
per second counts the tokens the requests hold at that step; the gaps
between steps up to it are the time per output token. The clock then
ends the call by raising ``WindowClosed`` out of it, so that no run pays
for draining requests that the window no longer measures.

Correctness: once the window has closed and the program's state is
freed, a sample of the finished requests drawn from the seed (the
longest among them) is run through the plain float32 reference, which
makes its weights again from the seed, and each served token's logit is
compared with the reference's best at that position.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from bench import harness
from bench.reference import stablelm_ref, weights

SAMPLE = 8          # requests compared with the reference


def program_arch(cfg: dict):
    """The program's architecture config with every size the benchmark's
    configuration states (for the published config this changes nothing)."""
    from repro.configs import get_arch
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return dataclasses.replace(
        get_arch(cfg["program_arch"]), n_layers=cfg["num_hidden_layers"],
        d_model=d, n_heads=heads, n_kv_heads=cfg["num_key_value_heads"],
        d_head=d // heads, d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], rope_frac=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]), qkv_bias=cfg["use_qkv_bias"],
        norm="ln", param_dtype=cfg["torch_dtype"],
        compute_dtype=cfg["torch_dtype"])


def make_params(cfg: dict, seed: int, model):
    """The program's parameter tree, made on the device in one jitted call
    by the benchmark's own seeded generator."""
    @jax.jit
    def build(root):
        g = weights.globals_(root, cfg)
        t = weights.stacked_layers(root, cfg)
        norm = lambda n: {"scale": t[f"{n}_scale"], "bias": t[f"{n}_bias"]}  # noqa: E731
        return {"embed": g["embed"], "head": g["head"],
                "final_norm": {"scale": g["final_scale"],
                               "bias": g["final_bias"]},
                "layers": {"ln1": norm("ln1"), "ln2": norm("ln2"),
                           "attn": {k: t[k] for k in ("wq", "wk", "wv", "wo")
                                    + weights.QKV_BIASES if k in t},
                           "mlp": {"w_in": t["w_up"], "w_gate": t["w_gate"],
                                   "w_out": t["w_down"]}}}

    root = harness.key_from_seed(seed)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(build, root)
    describe = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: (a.shape, str(a.dtype)), tree)
    if describe(want) != describe(got):
        raise harness.BenchError(
            f"the program's parameters are not the configuration's: "
            f"{describe(want)} != {describe(got)}")
    return jax.block_until_ready(build(root))


class WindowClosed(Exception):
    """Raised by the step clock to end ``run()`` once the window closes."""


class StepClock:
    """The engine's ``profiler`` collaborator: due on every step."""

    def __init__(self, on_step):
        self._on_step = on_step

    def bind(self, params, n_slots: int, max_seq: int) -> None:
        pass

    def due(self, step: int) -> bool:
        return True

    def on_step(self, step_us: float) -> None:
        self._on_step()


class Driver:
    def __init__(self, cell: str, config: dict, traffic: dict, seed: int):
        self.cell = cell
        self.cfg = config
        self.traffic = traffic
        self.seed = seed
        dep = config["deployment"]
        self.n_slots, self.max_seq = int(dep["n_slots"]), int(dep["max_seq"])
        self.win = None

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from repro.models import build_model
        from repro.serve import Request, ServeEngine
        self.Request = Request
        model = build_model(program_arch(self.cfg))
        params = make_params(self.cfg, self.seed, model)
        self.engine = ServeEngine(model, params, n_slots=self.n_slots,
                                  max_seq=self.max_seq,
                                  profiler=StepClock(self._on_step))
        if self.engine.mode != "token":
            raise harness.BenchError(f"engine mode {self.engine.mode!r}")
        # warm up this cell's shapes: one tiny request through run()
        self.engine.submit(Request(-1, np.array([1, 2], np.int32),
                                   max_new_tokens=2))
        self.engine.run()
        gen = harness.load_module("traffic", self.traffic["generator"])
        self.backlog = gen.Backlog(self.traffic, self.seed,
                                   self.cfg["vocab_size"], self.max_seq)
        self.finished0 = len(self.engine.batcher.finished)
        self.requests: dict = {}
        self.rejected = 0

    def _submit(self) -> None:
        ids, n_out = self.backlog.next()
        req = self.Request(len(self.requests), ids, max_new_tokens=n_out)
        self.requests[req.request_id] = req
        if not self.engine.submit(req):
            self.rejected += 1

    def _outstanding(self) -> int:
        done = len(self.engine.batcher.finished) - self.finished0
        return len(self.requests) - self.rejected - done

    # -- the window -----------------------------------------------------------

    def _on_step(self) -> None:
        win = self.win
        if win is None:
            return
        now = time.perf_counter()
        with win.span("bench.serve.hook"):
            self.times.append(now)
            active = live = feeding = 0
            for s in self.engine.batcher.slots:
                if not s.active:
                    continue
                first = self.first_step.setdefault(s.request_id, self.step)
                n = self.step - first + 1          # positions written so far
                active += 1
                live += n
                feeding += n < len(self.requests[s.request_id].prompt)
            self.per_step.append((active, live, feeding, win.tracing))
            self.step += 1
            if win.tick():
                self.tokens = sum(len(r.tokens) for r in
                                  self.requests.values())
                self.closed = True
            else:
                while self._outstanding() < self.backlog.size:
                    self._submit()
        if self.closed:
            self.wall_s = win.close()
            raise WindowClosed

    def window(self, win: harness.Window) -> dict:
        self.win, self.closed = win, False
        self.times, self.per_step, self.first_step = [], [], {}
        self.step = 0
        for _ in range(self.backlog.size):
            self._submit()
        t_open = win.open()
        try:
            self.engine.run()
            raise harness.BenchError("the backlog drained before the window "
                                     "closed")
        except WindowClosed:
            pass
        gaps = np.diff([t_open] + self.times).tolist()
        done = len(self.engine.batcher.finished) - self.finished0
        steps = len(self.times)
        return {
            "attempted": len(self.requests),
            "failed": self.rejected,
            "config": self.cfg,
            "window": {"wall_s": self.wall_s, "steps": steps,
                       "tokens": self.tokens, "gaps_s": gaps,
                       "traced_steps": sum(p[3] for p in self.per_step)},
            "serve": {"n_slots": self.n_slots, "per_step": self.per_step},
            "decode_module": "jit_decode_step",
            "notes": {"steps": steps, "tokens": self.tokens,
                      "longest_gap_ms": 1e3 * max(gaps),
                      "requests": len(self.requests), "completed": done,
                      "rejected": self.rejected},
        }

    def free(self) -> None:
        self.engine = None

    # -- correctness ----------------------------------------------------------

    def check(self) -> dict[str, float]:
        done = [r for r in self.requests.values()
                if len(r.tokens) == r.max_new_tokens]
        done.sort(key=lambda r: r.request_id)
        if not done:
            raise harness.BenchError("no request finished inside the window")
        longest = max(range(len(done)),
                      key=lambda i: len(done[i].prompt) + len(done[i].tokens))
        rest = [i for i in range(len(done)) if i != longest]
        rng = np.random.default_rng([self.seed, 1])
        pick = [longest] + sorted(rng.choice(
            rest, size=min(SAMPLE - 1, len(rest)), replace=False).tolist())
        sample = [(done[i].prompt, done[i].tokens) for i in pick]
        self.sample = sample
        gap, _ = stablelm_ref.widest_gap(
            self.cfg, harness.key_from_seed(self.seed), sample, self.max_seq)
        return {"logit_gap": gap}
