"""Serve a backlog, as ``serve_backlog`` does, on a latent-attention MoE
model (DeepSeek-V2): its weights, program configuration and mapping come
from ``mla_serving``, its counts from ``mla_counts`` and its reference from
``reference/deepseek_v2.py``; the engine, its admission, pages and decode
program are the ones every serve cell runs.

Besides the serve bookkeeping the driver reads the engine's on-device MoE
counters at the window's open and close (one host read each) for the
per-layer metrics.
"""

import importlib.util
import time
from pathlib import Path

import jax.numpy as jnp

import mla_serving
import traffic_gen
from reference import deepseek_v2
from serving import load_limits

_spec = importlib.util.spec_from_file_location(
    "bench_drivers_serve_backlog", Path(__file__).with_name("serve_backlog.py"))
backlog = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(backlog)

MOE_KEYS = ("steps", "moe_assignments", "moe_not_computed",
            "moe_decode_experts_hit")


class Driver(backlog.Driver):
    def __init__(self, run):
        # ServeDriver's bookkeeping with this architecture's sizes
        self.run = run
        self.arch = mla_serving.arch_of(run.model)
        self.cfg = mla_serving.program_config(run.model)
        self.dtype = jnp.dtype(run.model["dtypes"]["weights"])
        self.engine = None
        self.t0 = self.t_end = 0.0
        self.token_times, self.reqs = {}, {}
        self.counting = False
        self.admitted = set()
        self.decode = {"steps": 0, "lanes": 0, "context_tokens": 0}
        self.prefills = []
        self._seen_finished = 0
        self.moe = {}

    def setup(self) -> None:
        from repro.serve.engine import EngineConfig, Request, ServeEngine
        self.Request = Request
        run = self.run
        self.traffic = traffic_gen.generate(run.mix, run.seed, run.seconds,
                                            self.arch["vocab_size"])
        self.requests = self.traffic["requests"]
        t = time.perf_counter()
        params = mla_serving.to_program(
            self.arch, mla_serving.make(self.arch, run.seed, self.dtype))
        ecfg = run.model["program"]["engine"]
        self.engine = ServeEngine(self.cfg, params, EngineConfig(**ecfg))
        # the reference runs every request at the engine's longest context,
        # in whole blocks of its queries
        q = deepseek_v2.Q_BLOCK
        self.ref_width = -(-ecfg["max_pages_per_seq"] * ecfg["page_size"]
                           // q) * q
        self.setup_phases = {"weights_engine_s": time.perf_counter() - t}
        t = time.perf_counter()
        self._warm_up()
        self.setup_phases["warm_up_s"] = time.perf_counter() - t
        for r in self.requests:
            self.submit(r)
        self.step()                     # admit the first full batch

    def window(self, seconds: float) -> None:
        before = self.engine.stats
        start = {k: before[k] for k in MOE_KEYS}
        super().window(seconds)
        after = self.engine.stats
        self.moe = {k: after[k] - start[k] for k in MOE_KEYS}

    def layer_data(self) -> dict:
        out = super().layer_data()
        out["moe"] = dict(self.moe)
        return out

    def check(self):
        limits = load_limits(self.run)
        control = self.run.options.get("control")
        if control is not None and control not in self.CONTROLS:
            raise ValueError(f"no control {control!r}; a serve cell has "
                             f"{self.CONTROLS}")
        w = mla_serving.make(self.arch, self.run.seed, self.dtype)
        worst, compared = 0.0, 0
        for rid in self.check_sample():
            gaps = deepseek_v2.served_gaps(
                self.arch, w, self.requests[rid]["prompt"],
                self.reqs[rid].generated, self.ref_width,
                self.run.mix["output"]["max"], control=control is not None)
            judged = gaps[1] if control else gaps[0]
            worst = max(worst, float(judged.max()))
            compared += len(judged)
        del w
        checks = {"worst_gap_spreads": {"value": worst, "limit": limits[
                      "worst_gap_spreads"]},
                  "tokens_compared": {"value": compared, "limit": limits[
                      "min_tokens_compared"], "at_least": True},
                  "moe_not_computed": {"value": self.moe.get(
                      "moe_not_computed", 0), "limit": limits[
                      "moe_not_computed"]}}
        attempted, failed = self.attempted_failed()
        return checks, attempted, failed

