"""Driver of the MicroHH cells: the application's time step, launched
through ``WisdomKernel`` exactly as an application would.

One step is ``advec_u``, then ``diff_uvw``, then the jitted field update.
The window runs steps back to back; each eager ``WisdomKernel`` call
selects its configuration from wherever the program resolves wisdom and
blocks until its result is ready. The last step of the window is kept
and, once the window has closed, compared field by field with the plain
reference: each kernel's tendencies at the configuration the launcher
selected, and the update.
"""

from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp

from bench import harness, work
from bench.reference import stencils as ref


def _make_update(dt: float):
    f32 = jnp.float32

    @jax.jit
    def stencil_update(u, v, w, ut, dut, dvt, dwt):
        def step(x, t):
            return (x.astype(f32) + dt * t).astype(x.dtype)
        return (step(u, ut.astype(f32) + dut.astype(f32)),
                step(v, dvt.astype(f32)), step(w, dwt.astype(f32)))

    return stencil_update


class Driver:
    def __init__(self, cell: str, config: dict, traffic: dict, seed: int):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.grid = int(traffic["grid"])
        self.dtype = traffic["dtype"]
        self.dt = float(traffic["dt"])
        self.held = None

    def setup(self) -> None:
        from repro.core import WisdomKernel, get_kernel
        gen = harness.load_module("traffic", self.traffic["generator"])
        u, v, w, self.evisc, self.scal = gen.fields(
            self.traffic, self.seed, self.config["assumed"]["inverse_spacings"])
        self.state = (u, v, w)
        self.advec = WisdomKernel(get_kernel("advec_u"))
        self.diff = WisdomKernel(get_kernel("diff_uvw"))
        self.update = _make_update(self.dt)
        # warm up this cell's shapes: one whole step, result discarded
        jax.block_until_ready(self._step(u, v, w, harness.Window.no_span))

    def _step(self, u, v, w, span):
        with span("bench.launch.advec_u"):
            ut = self.advec(u, v, w, self.scal)
        with span("bench.launch.diff_uvw"):
            dut, dvt, dwt = self.diff(u, v, w, self.evisc, self.scal)
        with span("bench.update"):
            new = self.update(u, v, w, ut, dut, dvt, dwt)
        return (ut, dut, dvt, dwt), new

    def window(self, win: harness.Window) -> dict:
        a0, d0 = len(self.advec.stats), len(self.diff.stats)
        state, steps, traced, longest = self.state, 0, 0, 0.0
        self.state = None
        last = win.open()
        while True:
            traced += win.tracing
            with win.span("bench.step"):
                tend, new = self._step(*state, win.span)
            self.held = (state, tend, new)
            state = new
            steps += 1
            now = time.perf_counter()
            longest, last = max(longest, now - last), now
            if win.tick():
                break
        jax.block_until_ready(state)
        wall_s = win.close()
        del state, tend, new

        launches = self.advec.stats[a0:] + self.diff.stats[d0:]
        n, dt = self.grid, self.dtype
        return {
            "attempted": steps, "failed": 0,
            "window": {"wall_s": wall_s, "steps": steps,
                       "traced_steps": traced},
            "launches": [{"kernel": s.kernel, "tier": s.tier,
                          "select_s": s.select_s, "cached": s.cached}
                         for s in launches],
            # device order of one step's modules, for the trace reduction
            "step_modules": ["advec_u", "diff_uvw", "jit_stencil_update"],
            "work": {"advec_u": work.stencil("advec_u", n, dt),
                     "diff_uvw": work.stencil("diff_uvw", n, dt),
                     "step": work.stencil_step(n, dt)},
            "notes": {"steps": steps, "longest_step_ms": 1e3 * longest,
                      "compiles_in_window": sum(not s.cached
                                                for s in launches),
                      "tiers": dict(collections.Counter(
                          f"{s.kernel}:{s.tier}" for s in launches)),
                      "configs": {s.kernel: s.config for s in launches[:1]
                                  + launches[-1:]}},
        }

    def free(self) -> None:
        self.advec = self.diff = self.update = None

    def check(self) -> dict[str, float]:
        (u, v, w), (ut, dut, dvt, dwt), new = self.held
        self.held = None
        want = ref.advec_u(u, v, w, self.scal)
        advec = float(ref.rel_err(ut, want))
        del want
        want = ref.diff_uvw(u, v, w, self.evisc, self.scal)
        diff = max(float(ref.rel_err(g, r)) for g, r in zip((dut, dvt, dwt),
                                                            want))
        del want
        want = ref.update(u, v, w, ut, dut, dvt, dwt, self.dt)
        upd = max(float(ref.rel_err(g, r)) for g, r in zip(new, want))
        return {"advec_u_err": advec, "diff_uvw_err": diff,
                "update_err": upd}
