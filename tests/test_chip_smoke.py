"""No-chip rehearsal of ``chip_smoke.py``: its phase functions at the smoke
config on the CPU, with the Pallas kernels in interpret mode, and its refusal
to report a result when JAX finds no TPU."""

import importlib.util
from pathlib import Path

import pytest

from repro.configs.registry import get_smoke_config


@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("smollm-135m")


def test_serve_phase_matches_float32_reference(chip_smoke, cfg):
    out = chip_smoke.phase_serve(cfg, prompt_lens=(8, 40), new_tokens=6,
                                 page_size=4, prefill_bucket=16)
    assert out["requests"] == 8 and out["tokens"] == 8 * 6
    assert out["worst_gap_spreads"] <= out["tolerance_spreads"]


def test_paging_phase_is_bitwise_and_oversubscribed(chip_smoke, cfg):
    out = chip_smoke.phase_paging(cfg, impl="interpret", page_size=4096,
                                  seq=16)
    assert out["bitwise_equal"] and out["staging_copies"] == 0
    assert out["params_buffer_bytes"] < out["params_bytes"]
    # pages really moved through the device pool's scatter/gather
    assert out["params_region"]["evictions"] > 0


def test_main_refuses_without_a_tpu(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err
    for line in out.splitlines():       # no result line of any kind
        assert not line.startswith("{"), line
