"""Serve cells at smoke size on the CPU: a sound run is correct, its control
put in the program's place comes out not correct, and each fault planted in
the timed path makes the run come out not correct."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

import cells

DECODE = "qwen2-1.5b.decode_long"
CHAT = "qwen2-1.5b.chat_prefix"


@pytest.mark.parametrize("cell", [DECODE, CHAT])
def test_cell_runs_correct_with_metrics(cell):
    out = cells.run(cell, seconds=2.0 if cell == CHAT else 0.3)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compiles_in_window"]["backend_compile_duration"] == 0
    names = {"itl_p95_ms", "setup_s"}
    if cell == DECODE:
        names.add("tokens_per_s")
    assert set(out["metrics"]) == names
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"


def test_traced_open_loop_run_reports_ttft_per_layer():
    out = cells.run(CHAT, seconds=2.0, trace=True)
    assert out["correct"], out["checks"]
    # engine.prefill_ms reads the TPU's program names, which a CPU trace lacks
    assert {"device_idle.chat", "ttft_p95_ms.chat"} <= set(out["metrics"])
    assert out["metrics"]["ttft_p95_ms.chat"]["value"] > 0


@pytest.mark.parametrize("cell", [DECODE, CHAT])
def test_control_is_not_correct(cell):
    out = cells.run(cell, seconds=2.0 if cell == CHAT else 0.3,
                    options={"control": "fp8"})
    assert not out["correct"], out["checks"]
    gap = out["checks"]["worst_gap_spreads"]
    assert gap["value"] > gap["limit"]


def test_traced_run_reports_device_fields():
    out = cells.run(DECODE, trace=True)
    assert out["correct"]
    assert "device_idle.serve" in out["metrics"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _kv_not_written(orig):
    def step(cfg, params, cache, tokens, cur_pos):
        logits, _ = orig(cfg, params, cache, tokens, cur_pos)
        return logits, cache
    return step


def _token_altered(orig):
    def step(cfg, params, cache, tokens, cur_pos):
        logits, new = orig(cfg, params, cache, tokens, cur_pos)
        return logits.at[0].set(jnp.roll(logits[0], 1)), new
    return step


def _half_batch(orig):
    def step(cfg, params, cache, tokens, cur_pos):
        logits, new = orig(cfg, params, cache, tokens, cur_pos)
        half = logits.shape[0] // 2
        return jnp.concatenate([logits[:half], logits[:half]]), new
    return step


@pytest.mark.parametrize("fault", [_kv_not_written, _token_altered, _half_batch])
def test_fault_in_decode_step_is_not_correct(fault, monkeypatch):
    from repro.models import transformer as T
    monkeypatch.setattr(T, "decode_step", fault(T.decode_step))
    out = cells.run(DECODE)
    assert not out["correct"], out["checks"]


def test_sweep_rates_serve_the_cells_requests():
    import traffic_gen
    mix = json.loads((cells.BENCH / "traffic" / "chat_prefix.json").read_text())
    base = traffic_gen.generate(mix, cells.SEED, 51, 1000)["requests"]
    n = len(base)
    for rate in (4.0, 6.0):
        seconds = n / rate
        other = traffic_gen.generate(dict(mix, rate_per_s=rate), cells.SEED,
                                     seconds, 1000)["requests"]
        assert ([len(r["prompt"]) for r in other]
                == [len(r["prompt"]) for r in base])
        assert max(r["due"] for r in other) < seconds


def test_command_refuses_a_machine_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(cells.BENCH / "run.py"), "--workload", DECODE,
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cells.BENCH.parent, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_benchmark_file_names_every_file():
    spec = json.loads((cells.BENCH.parent / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (cells.BENCH.parent / c["file"]).is_file()
    for w in spec["workloads"]:
        mix = json.loads((cells.BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert (cells.BENCH / "drivers" / f"{mix['driver']}.py").is_file()
        assert (cells.BENCH / "limits" / f"{w['name']}.json").is_file()
    for m in spec["per_layer"]:
        assert (cells.BENCH / "metrics" / f"{m['name']}.py").is_file()
