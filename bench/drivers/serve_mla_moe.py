"""Driver of the DeepSeek-V2 serving cell: ``ServeEngine.run`` on the
configuration's layers at published widths, holding its share of the
experts, fed a constant backlog of requests.

The window is ``serve_batch``'s: the engine's own step clock records
every step's time and slots and closes the window by raising out of
``run()``; the engine must serve in token mode. The record adds the held
experts' work: the (token, k) pairs they computed in the window, from
the engine's device tally (``ServeEngine.tallies``, pulled once after
the warm-up and once at the window's close), and the rows their
matmuls computed (``models.moe.expert_rows``, recorded when the step was
traced, per MoE layer and step).

Correctness: as ``serve_batch``, against the plain float32 DeepSeek-V2
reference (``bench/reference/deepseek_v2_ref.py``), which makes its
weights again from the seed; teacher-forced on the served experts as on
the served tokens. The program keeps every token's experts in its cache
(``moe_picks``, written in place each step and pulled once at the
window's close), so the sample is drawn from the requests of the arena
the window ended in. Two readings: ``logit_gap`` (served tokens) and
``route_gap`` (served experts, in router logits). ``control()`` gives the
float8 control's readings on the same sample; ``routing()`` counts the
sample's (token, layer) expert choices and how many differ from the
reference's own.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from bench import harness
from bench.reference import deepseek_v2_ref, dsv2_weights as weights

serve_batch = harness.load_module("drivers", "serve_batch")

SAMPLE = serve_batch.SAMPLE


def program_arch(cfg: dict):
    """The program's architecture config with every size the benchmark's
    configuration states, the experts this chip holds among them (for
    the published config, cut to its depth, this changes nothing)."""
    from repro.configs import get_arch
    try:
        from repro.models.config import MLACfg, YarnCfg
        arch = get_arch(cfg["program_arch"])
        y = cfg["rope_scaling"]
        moe = dataclasses.replace(
            arch.moe, n_routed=cfg["published"]["n_routed_experts"],
            top_k=cfg["num_experts_per_tok"],
            d_expert=cfg["moe_intermediate_size"],
            n_shared=cfg["n_shared_experts"], n_group=cfg["n_group"],
            topk_group=cfg["topk_group"], norm_topk=cfg["norm_topk_prob"],
            routed_scale=float(cfg["routed_scaling_factor"]),
            held=(cfg["held_experts"]["first"], cfg["n_routed_experts"]))
        rope = YarnCfg(
            factor=float(y["factor"]),
            original_max_seq=y["original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=y["mscale"], mscale_all_dim=y["mscale_all_dim"])
    except (ImportError, TypeError) as e:
        raise harness.BenchError(
            f"the program has no held-expert layer or YaRN: {e}") from e
    return dataclasses.replace(
        arch, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), rope_scaling=rope,
        mla=MLACfg(q_lora=cfg["q_lora_rank"], kv_lora=cfg["kv_lora_rank"],
                   d_nope=cfg["qk_nope_head_dim"],
                   d_rope=cfg["qk_rope_head_dim"], d_v=cfg["v_head_dim"]),
        moe=moe, dense_layers=tuple(range(cfg["first_k_dense_replace"])),
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"])


def make_params(cfg: dict, seed: int, model):
    """The program's parameter tree, made on the device by the benchmark's
    own seeded generator: the dense prefix and the globals in one jitted
    call, the expert layers in another, one layer at a time."""
    n_dense = cfg["first_k_dense_replace"]

    def attn(t):
        return {"ln1": {"scale": t["ln1_scale"]},
                "ln2": {"scale": t["ln2_scale"]},
                "mla": {"wq_a": t["wq_a"], "q_norm": {"scale": t["q_norm"]},
                        "wq_b": t["wq_b"], "wkv_a": t["wkv_a"],
                        "kv_norm": {"scale": t["kv_norm"]},
                        "wk_b": t["wk_b"], "wv_b": t["wv_b"],
                        "wo": t["wo"]}}

    @jax.jit
    def build_dense(root):
        g = weights.globals_(root, cfg)
        prefix = []
        for l in range(n_dense):
            t = weights._layer_tensors(root, l, cfg, False)
            prefix.append(attn(t) | {"mlp": {"w_in": t["w_up"],
                                             "w_gate": t["w_gate"],
                                             "w_out": t["w_down"]}})
        return {"embed": g["embed"], "head": g["head"],
                "final_norm": {"scale": g["final_scale"]},
                "dense_prefix": prefix}

    @jax.jit
    def build_moe(root):
        t = weights.stacked_moe_layers(root, cfg)
        return attn(t) | {"moe": {
            "router": t["router"], "w_in": t["expert_up"],
            "w_gate": t["expert_gate"], "w_out": t["expert_down"],
            "shared_in": t["shared_up"], "shared_gate": t["shared_gate"],
            "shared_out": t["shared_down"]}}

    root = harness.key_from_seed(seed)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(build_dense, root) | {
        "layers": jax.eval_shape(build_moe, root)}
    describe = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: (a.shape, str(a.dtype)), tree)
    if describe(want) != describe(got):
        raise harness.BenchError(
            f"the program's parameters are not the configuration's: "
            f"{describe(want)} != {describe(got)}")
    params = jax.block_until_ready(build_dense(root))
    params["layers"] = jax.block_until_ready(build_moe(root))
    return params


class Driver(serve_batch.Driver):

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from repro.models import build_model, moe
        from repro.serve import Request, ServeEngine
        self.Request = Request
        model = dataclasses.replace(build_model(program_arch(self.cfg)),
                                    record_routing=True)
        if not model.decode_supports_start:
            raise harness.BenchError("the program serves MLA in cohorts")
        params = make_params(self.cfg, self.seed, model)
        self.engine = ServeEngine(
            model, params, n_slots=self.n_slots, max_seq=self.max_seq,
            profiler=serve_batch.StepClock(self._on_step))
        if self.engine.mode != "token":
            raise harness.BenchError(f"engine mode {self.engine.mode!r}")
        # warm up this cell's shapes: one tiny request through run()
        self.engine.submit(Request(-1, np.array([1, 2], np.int32),
                                   max_new_tokens=2))
        self.engine.run()
        self.rows_per_call = moe.expert_rows[(self.n_slots, 1)]
        self.routed0 = self.engine.tallies()["moe_routed"]
        gen = harness.load_module("traffic", self.traffic["generator"])
        self.backlog = gen.Backlog(self.traffic, self.seed,
                                   self.cfg["vocab_size"], self.max_seq)
        self.finished0 = len(self.engine.batcher.finished)
        self.requests: dict = {}
        self.rejected = 0

    # -- the window -----------------------------------------------------------

    def _on_step(self) -> None:
        if self.win is not None:
            for i, s in enumerate(self.engine.batcher.slots):
                if s.active:
                    self.placed.setdefault(s.request_id,
                                           (i, s.start, self.engine.arenas))
        super()._on_step()

    def window(self, win: harness.Window) -> dict:
        self.placed = {}
        record = super().window(win)
        self.picks = np.asarray(self.engine.cache["moe_picks"])
        self.arena = self.engine.arenas
        routed = self.engine.tallies()["moe_routed"] - self.routed0
        n_moe = (self.cfg["num_hidden_layers"]
                 - self.cfg["first_k_dense_replace"])
        record["experts"] = {
            "routed": routed,
            "rows": self.rows_per_call * n_moe * record["window"]["steps"]}
        notes = record["notes"]
        notes["expert_rows_per_step"] = self.rows_per_call * n_moe
        q = np.quantile(record["window"]["gaps_s"], [0.5, 0.9, 0.95, 0.99])
        notes["gap_ms_p50_p90_p95_p99"] = [round(1e3 * v, 3) for v in q]
        return record

    # -- correctness ----------------------------------------------------------

    def _sample(self) -> list:
        """The finished requests compared with the reference, among those
        of the arena the window ended in (whose experts the cache holds):
        the longest and ``SAMPLE - 1`` more drawn from the seed."""
        done = [r for r in self.requests.values()
                if len(r.tokens) == r.max_new_tokens
                and self.placed.get(r.request_id, (0, 0, -1))[2] == self.arena]
        done.sort(key=lambda r: r.request_id)
        if not done:
            raise harness.BenchError("no request finished inside the window")
        longest = max(range(len(done)),
                      key=lambda i: len(done[i].prompt) + len(done[i].tokens))
        rest = [i for i in range(len(done)) if i != longest]
        rng = np.random.default_rng([self.seed, 1])
        pick = [longest] + sorted(rng.choice(
            rest, size=min(SAMPLE - 1, len(rest)), replace=False).tolist())
        sample = [done[i] for i in pick]
        self.sample_picks = []
        for r in sample:
            slot, start, _ = self.placed[r.request_id]
            n = len(r.prompt) + len(r.tokens) - 1
            self.sample_picks.append(self.picks[:, slot, start:start + n])
        return [(r.prompt, r.tokens) for r in sample]

    def check(self) -> dict[str, float]:
        self.sample = self._sample()
        got = deepseek_v2_ref.compare(
            self.cfg, harness.key_from_seed(self.seed), self.sample,
            picks=self.sample_picks)
        self.routed_choices = got["routing"]
        return {"logit_gap": got["logit_gap"], "route_gap": got["route_gap"]}

    def routing(self) -> dict[str, int]:
        """(token, layer) expert choices of the compared sample, and how
        many of the program's differ from the reference's own."""
        return self.routed_choices

    def control(self) -> dict[str, float]:
        """The float8 control's readings on the sample ``check`` compared."""
        got = deepseek_v2_ref.compare(
            self.cfg, harness.key_from_seed(self.seed), self.sample,
            control=True)
        return {"logit_gap": got["logit_gap"], "route_gap": got["route_gap"],
                "served_tokens": got["served_tokens"]}
