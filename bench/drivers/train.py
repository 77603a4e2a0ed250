"""Out-of-core AdamW: ``OOCTrainer(paged=True)`` with its parameters and
moments behind page buffers smaller than the state, as the configuration
file sizes them.

Set-up builds the trainer, loads the benchmark's weights from the seed and
drives it through its first three steps with the window's own ``step()``
call and feed; the reference follows those three.  The window then runs
whole steps, each on its own rows, until ``seconds`` have passed; ``step_s``
is the time of all of them over their number.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

import traffic_gen
import weights
from reference import adamw
from serving import arch_of, load_limits, program_config, to_program

CHECKED_STEPS = 3
# what calibration puts in the program's place: the reference with bf16
# parameters and moments (the precision below the configuration's), and
# the reference with half of each batch left out
CONTROLS = {"bf16_state": {"state_dtype": "bfloat16"},
            "half_batch": {"half_batch": True}}


def from_program(cfg, tree: dict) -> dict:
    """The program's parameter tree in the benchmark layout (padding cut)."""
    seg = tree["segments"][0]
    q = cfg.num_heads * cfg.head_dim
    layers = {"ln1": seg["ln1"], "ln2": seg["ln2"],
              "wq": seg["attn"]["wq"][..., :q], "wk": seg["attn"]["wk"],
              "wv": seg["attn"]["wv"], "wo": seg["attn"]["wo"][:, :q],
              **seg["mlp"]}
    out = {"embed": tree["embed"][:cfg.vocab_size], "layers": layers,
           "final_norm": tree["final_norm"]}
    if "lm_head" in tree:
        out["lm_head"] = tree["lm_head"][:, :cfg.vocab_size]
    return out


class Driver:
    def __init__(self, run):
        self.run = run
        self.arch = arch_of(run.model)
        self.cfg = program_config(run.model)
        self.trainer = None
        self.step_times, self.sweep_times = [], []
        self.faults = {}

    def batch(self, i: int) -> dict:
        mix = self.run.mix
        rng = traffic_gen.rng_for(self.run.seed, 100 + i)
        toks = rng.integers(0, self.arch["vocab_size"],
                            size=(mix["batch"], mix["seq_len"] + 1))
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32)}

    def _timed_sweep(self, sweep):
        def timed():
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.sweep"):
                sweep()
            self.sweep_times.append(time.perf_counter() - t)
        return timed

    def _faults(self) -> int:
        tr = self.trainer
        return sum(r.stats()["demand_faults"]
                   for r in (tr._params.region, tr.opt.region))

    def setup(self) -> None:
        from repro.train.ooc import OOCTrainer, OOCTrainerConfig
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import TrainConfig
        model = self.run.model
        t = time.perf_counter()
        w = weights.make(self.arch, self.run.seed, jnp.float32)
        params = jax.device_get(to_program(self.cfg, w))
        self.w0 = jax.device_get(w)
        del w
        tcfg = TrainConfig(optimizer=AdamWConfig(**model["optimizer"]),
                           z_loss_coeff=model["objective"]["z_loss_coeff"])
        tr = OOCTrainer(self.cfg, tcfg,
                        OOCTrainerConfig(**model["program"]["trainer"]),
                        rng=weights.key_for(self.run.seed, 1), paged=True)
        self.trainer = tr
        zeros = jax.tree.map(np.zeros_like, params)
        tr.load_state_dict({"params": params, "opt": {"m": zeros, "v": zeros},
                            "step": 0})
        del params, zeros
        tr._apply_pending = self._timed_sweep(tr._apply_pending)
        self.setup_phases = {"weights_trainer_s": time.perf_counter() - t}
        t = time.perf_counter()
        b1 = model["optimizer"]["beta1"]
        self.losses = []
        for i in range(CHECKED_STEPS):
            self.losses.append(float(tr.step(self.batch(i))["loss"]))
            if i == 0:
                m = from_program(self.cfg, tr.state_dict()["opt"]["m"])
                self.first_grad = adamw.leaf_norms(
                    jax.tree.map(lambda a: a / (1 - b1), m))
        p = from_program(self.cfg, tr.state_dict()["params"])
        self.change = adamw.leaf_norms(jax.tree.map(
            lambda a, b: np.asarray(a, np.float32) - b, p, self.w0))
        self.sweep_times.clear()
        self.setup_phases["checked_steps_s"] = time.perf_counter() - t

    def window(self, seconds: float) -> None:
        tr = self.trainer
        f0 = self._faults()
        i = CHECKED_STEPS
        self.t0 = time.perf_counter()
        while time.perf_counter() - self.t0 < seconds:
            b = self.batch(i)
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                tr.step(b)
            self.step_times.append(time.perf_counter() - t)
            i += 1
        self.t_end = time.perf_counter()
        self.faults = {"demand_faults": self._faults() - f0}

    def after_window(self) -> None:
        pass

    def end_to_end(self) -> dict:
        return {"step_s": (self.t_end - self.t0) / len(self.step_times)}

    def layer_data(self) -> dict:
        tr = self.trainer
        mix = self.run.mix
        leaf_shapes = [s["shape"] for s in tr._p_specs]
        return {"arch": self.arch, "steps": len(self.step_times),
                "step_s": (self.t_end - self.t0) / len(self.step_times),
                "sweep_s": list(self.sweep_times),
                "tokens_per_step": mix["batch"] * mix["seq_len"],
                "demand_faults": self.faults["demand_faults"],
                "page_size": self.run.model["program"]["trainer"]["page_size"],
                "param_leaf_shapes": leaf_shapes}

    def release(self) -> None:
        if self.trainer is not None:
            self.trainer.close()
            self.trainer = None

    def check(self):
        model = self.run.model
        w0 = jax.tree.map(jnp.asarray, self.w0)
        batches = [self.batch(i) for i in range(CHECKED_STEPS)]
        args = (self.arch, model["objective"], model["optimizer"], w0, batches)
        ref = adamw.train(*args)
        control = self.run.options.get("control")
        if control is None:
            prog = {"losses": self.losses, "grad": self.first_grad,
                    "change": self.change}
        elif control in CONTROLS:       # the control in the program's place
            prog = adamw.train(*args, **CONTROLS[control])
        else:
            raise ValueError(f"no control {control!r}; the training cell "
                             f"has {sorted(CONTROLS)}")
        got = adamw.readings(prog, ref)
        limits = load_limits(self.run)
        checks = {f"{k}_gap": {"value": got[k], "limit": limits[f"{k}_gap"],
                               "where": got[f"{k}_at"]}
                  for k in ("loss", "grad", "change")}
        n = CHECKED_STEPS + len(self.step_times)
        return checks, n, 0
