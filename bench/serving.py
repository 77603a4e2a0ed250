"""What the serve drivers share: the engine built from a configuration file,
token times taken by the harness after each ``ServeEngine.step()``, the
serve metrics, and the comparison of served tokens with the reference.

``step()`` ends in a host read of the chosen tokens, so the time taken
when it returns is the time those tokens were complete.
"""

from __future__ import annotations

import json
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import traffic_gen
import weights
from reference import llm

ARCH_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
             "head_dim", "intermediate_size", "num_hidden_layers",
             "vocab_size", "qkv_bias", "tie_word_embeddings", "rms_norm_eps",
             "rope_theta")


def arch_of(model: dict) -> dict:
    """The published sizes of a configuration file, as the references and
    the counts read them."""
    return {k: model[k] for k in ARCH_KEYS if k in model}


def program_config(model: dict):
    """The program's model configuration: the published sizes of the file,
    its dtypes, and the program-only settings under ``program.model``."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=model["name"], family="dense",
        num_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        qkv_bias=model["qkv_bias"], rope_theta=model["rope_theta"],
        tie_embeddings=model["tie_word_embeddings"],
        norm_eps=model["rms_norm_eps"], act="silu",
        param_dtype=model["dtypes"]["weights"],
        compute_dtype=model["dtypes"]["compute"],
        **model["program"]["model"])


def to_program(cfg, w: dict) -> dict:
    """The benchmark layout in the program's parameter tree (padded heads
    and vocabulary rows are zeros, which the program masks or ignores)."""
    pad_h = (cfg.padded_heads - cfg.num_heads) * cfg.head_dim

    def build(w):
        lay = w["layers"]
        emb = jnp.pad(w["embed"], ((0, cfg.padded_vocab - cfg.vocab_size), (0, 0)))
        attn = {"wq": jnp.pad(lay["wq"], ((0, 0), (0, 0), (0, pad_h))),
                "wk": lay["wk"], "wv": lay["wv"],
                "wo": jnp.pad(lay["wo"], ((0, 0), (0, pad_h), (0, 0)))}
        if "bq" in lay:
            attn.update(bq=jnp.pad(lay["bq"], ((0, 0), (0, pad_h))),
                        bk=lay["bk"], bv=lay["bv"])
        seg = {"ln1": lay["ln1"], "attn": attn, "ln2": lay["ln2"],
               "mlp": {"w_gate": lay["w_gate"], "w_up": lay["w_up"],
                       "w_down": lay["w_down"]}}
        out = {"embed": emb, "segments": [seg], "final_norm": w["final_norm"]}
        if "lm_head" in w:
            out["lm_head"] = jnp.pad(
                w["lm_head"], ((0, 0), (0, cfg.padded_vocab - cfg.vocab_size)))
        return out

    return jax.jit(build)(w)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) of a non-empty sample."""
    v = sorted(values)
    return float(v[max(0, int(np.ceil(q / 100.0 * len(v))) - 1)])


def load_limits(run) -> dict:
    """The cell's limits on its compared numbers (``limits/<cell>.json``),
    with its ``smoke`` overrides in a smoke-size run."""
    lim = json.loads((run.root / "limits" / f"{run.cell['name']}.json")
                     .read_text())
    smoke = lim.pop("smoke", {})
    return {**lim, **smoke} if run.smoke else lim


class ServeDriver:
    """Set-up and bookkeeping of a serve cell; subclasses drive the window."""

    def __init__(self, run):
        self.run = run
        self.arch = arch_of(run.model)
        self.cfg = program_config(run.model)
        self.dtype = jnp.dtype(run.model["dtypes"]["weights"])
        self.engine = None
        self.t0 = self.t_end = 0.0
        self.token_times: Dict[int, List[float]] = {}   # new tokens only
        self.reqs: Dict[int, object] = {}
        self.counting = False              # inside the window
        self.admitted: set = set()
        self.decode = {"steps": 0, "lanes": 0, "context_tokens": 0}
        self.prefills: List[tuple] = []    # (new tokens, tokens already held)
        self._seen_finished = 0

    # ------------------------------------------------------------- set-up

    def setup(self) -> None:
        from repro.serve.engine import EngineConfig, Request, ServeEngine
        self.Request = Request
        run = self.run
        self.traffic = traffic_gen.generate(run.mix, run.seed, run.seconds,
                                            self.arch["vocab_size"])
        self.requests = self.traffic["requests"]
        t = time.perf_counter()
        w = weights.make(self.arch, run.seed, self.dtype)
        params = to_program(self.cfg, w)
        del w
        ecfg = run.model["program"]["engine"]
        self.engine = ServeEngine(self.cfg, params, EngineConfig(**ecfg))
        # the reference runs every request at the engine's longest context
        self.ref_width = -(-ecfg["max_pages_per_seq"] * ecfg["page_size"]
                           // 128) * 128
        self.setup_phases = {"weights_engine_s": time.perf_counter() - t}
        t = time.perf_counter()
        for p in self.traffic["prefixes"]:
            self.engine.register_prefix(p)
        self.setup_phases["prefixes_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self._warm_up()
        self.setup_phases["warm_up_s"] = time.perf_counter() - t

    def _warm_up(self) -> None:
        """One prompt of each length and prefix in this run once through
        admission, prefill, the install into pool pages and one decode
        step: the shapes the window will use, compiled or read from the
        cache now."""
        eng = self.engine
        kinds = {(len(r["prompt"]), r["prefix"]): r for r in self.requests}
        for r in kinds.values():
            eng.submit(self.Request(rid=10**9 + r["rid"], prompt=r["prompt"],
                                    max_new_tokens=1))
        eng.run_until_drained(max_steps=10 * len(kinds) + 10)
        if eng.waiting or eng.active:
            raise RuntimeError("warm-up did not drain")
        eng.finished.clear()

    def submit(self, r: dict) -> None:
        self.engine.submit(self.Request(rid=r["rid"], prompt=r["prompt"],
                                        max_new_tokens=r["max_new_tokens"],
                                        submitted_at=time.time()))

    # -------------------------------------------------------------- steps

    def step(self) -> None:
        eng = self.engine
        with jax.profiler.TraceAnnotation("bench.step"):
            eng.step()
        now = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.observe"):
            self._observe(now)

    def _observe(self, now: float) -> None:
        eng = self.engine
        fresh = eng.finished[self._seen_finished:]
        self._seen_finished = len(eng.finished)
        lanes = ctx = 0
        for req in list(eng.active.values()) + fresh:
            rid = req.rid
            if rid not in self.admitted and req.admitted_at is not None:
                self.admitted.add(rid)
                if self.counting:
                    self._note_prefill(req)
            self.reqs[rid] = req
            times = self.token_times.setdefault(rid, [])
            n = len(req.generated)
            if n > len(times):
                times.extend([now] * (n - len(times)))
                lanes += 1
                ctx += len(req.prompt) + n - 1
        if lanes and self.counting:
            self.decode["steps"] += 1
            self.decode["lanes"] += lanes
            self.decode["context_tokens"] += ctx

    def _note_prefill(self, req) -> None:
        r = self.requests[req.rid]
        held = (len(self.traffic["prefixes"][r["prefix"]])
                if r["prefix"] >= 0 else 0)
        # prompt[:-1] is prefilled; the last prompt token is the first decode
        self.prefills.append((len(req.prompt) - 1 - held, held))

    # ------------------------------------------------------------- metrics

    def window_tokens(self):
        """(token count, inter-token gaps) inside [t0, t_end]."""
        count, gaps = 0, []
        for times in self.token_times.values():
            inside = [t for t in times if self.t0 <= t <= self.t_end]
            count += len(inside)
            gaps.extend(np.diff(inside).tolist())
        return count, gaps

    def end_to_end(self) -> dict:
        count, gaps = self.window_tokens()
        span = self.t_end - self.t0
        out = {"tokens_per_s": count / span}
        if gaps:
            out["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
        return out

    def layer_data(self) -> dict:
        return {"arch": self.arch, "itemsize": self.dtype.itemsize,
                "decode": dict(self.decode), "prefills": list(self.prefills)}

    def release(self) -> None:
        self.engine = None

    # --------------------------------------------------------------- check

    def check_sample(self) -> List[int]:
        """Requests to compare: the one with most served tokens, then others
        drawn from the seed among those ``candidates()`` gives."""
        cand = sorted(self.candidates())
        if not cand:
            return []
        longest = max(cand, key=lambda rid: len(self.reqs[rid].generated))
        rest = [r for r in cand if r != longest]
        rng = traffic_gen.rng_for(self.run.seed, 2)
        k = min(len(rest), self.run.mix["check"]["requests"] - 1)
        return [longest] + [rest[i] for i in rng.choice(len(rest), k,
                                                        replace=False)]

    # the control: the reference in float8 picks each token in the
    # program's place, at the positions of the same prompts and tokens
    CONTROLS = ("fp8",)

    def check(self):
        limits = load_limits(self.run)
        control = self.run.options.get("control")
        if control is not None and control not in self.CONTROLS:
            raise ValueError(f"no control {control!r}; a serve cell has "
                             f"{self.CONTROLS}")
        w = weights.make(self.arch, self.run.seed, self.dtype)
        worst, compared = 0.0, 0
        for rid in self.check_sample():
            gaps = llm.served_gaps(self.arch, w, self.requests[rid]["prompt"],
                                   self.reqs[rid].generated, self.ref_width,
                                   self.run.mix["output"]["max"],
                                   control=control is not None)
            judged = gaps[1] if control else gaps[0]
            worst = max(worst, float(judged.max()))
            compared += len(judged)
        del w
        checks = {"worst_gap_spreads": {"value": worst, "limit": limits[
                      "worst_gap_spreads"]},
                  "tokens_compared": {"value": compared, "limit": limits[
                      "min_tokens_compared"], "at_least": True}}
        attempted, failed = self.attempted_failed()
        return checks, attempted, failed
