"""Compiles of the chip path for a TPU v5e, without a chip.

The TPU compiler is installed with jax; it compiles for a described v5e
topology and refuses what the chip would refuse (block shapes off the
(8, 128) tiling, programs that do not fit).  Nothing runs, so these tests
say nothing about results or times — ``chip_smoke.py`` on the chip does.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""

import importlib.util
import re
from functools import partial
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.page_gather import kernel as page_kernels
from repro.kernels.page_gather import ops as page_ops
from repro.models import transformer as T
from repro.serve.engine import EngineConfig, ServeEngine, decode_program


@pytest.fixture(scope="module")
def one_chip():
    """One v5e chip of a described 2x2 host, with the persistent compile
    cache off (an entry compiled for a described chip cannot be read back
    here, and would only warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharding), tree)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# 64 KiB fp32 pages as the weight pool lays them out ([P, rows, 128]) and
# as a flat [P, elems] pool; 512 KiB pages as chip_smoke's trainer uses them.
@pytest.mark.parametrize("pool_shape", [
    (1024, 128, 128),
    (1024, 16384),
    (256, 1024, 128),
])
@pytest.mark.parametrize("op", ["gather", "scatter"])
def test_page_kernels_compile_for_v5e(one_chip, pool_shape, op):
    n = 64
    pool = _sds(pool_shape, jnp.float32, one_chip)
    ids = _sds((n,), jnp.int32, one_chip)
    if op == "gather":
        compiled = page_kernels.page_gather.lower(pool, ids).compile()
    else:
        pages = _sds((n,) + pool_shape[1:], jnp.float32, one_chip)
        compiled = page_kernels.page_scatter.lower(pool, ids, pages).compile()
    _assert_kernel(compiled)


# DeepSeek-V2-Lite's routed experts read in place: four MoE layers' 64
# experts as one [256, k, n] stack; a decode step's 32 lanes x top-6 rows
# and a 13,312-token prefill bucket's.
@pytest.mark.parametrize("m", [192, 13312 * 6])
@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_grouped_matmul_compiles_for_v5e(one_chip, m, k, n):
    args = (_sds((m, k), jnp.bfloat16, one_chip),
            _sds((256, k, n), jnp.bfloat16, one_chip),
            _sds((256,), jnp.int32, one_chip))
    compiled = jax.jit(partial(grouped_matmul, impl="pallas")).lower(
        *args).compile()
    _assert_kernel(compiled)
    assert compiled.out_info.shape == (m, n)


@pytest.fixture(scope="module")
def smollm(one_chip):
    """smollm-135m's parameters and engine caches as shapes on one chip."""
    cfg = get_config("smollm-135m")
    ecfg = EngineConfig(max_batch=8, page_size=16, num_pages=512,
                        max_pages_per_seq=32)
    params = jax.eval_shape(partial(T.init_params, cfg), jax.random.key(0))
    engine = SimpleNamespace(cfg=cfg, ecfg=ecfg, plan=cfg.decoder_plan())
    caches = jax.eval_shape(partial(ServeEngine._init_caches, engine))
    return cfg, ecfg, _on(params, one_chip), _on(caches, one_chip)


def test_engine_decode_step_compiles_for_v5e(one_chip, smollm):
    cfg, ecfg, params, caches = smollm
    b = ecfg.max_batch
    compiled = jax.jit(partial(T.decode_step, cfg)).lower(
        params, caches, _sds((b,), jnp.int32, one_chip),
        _sds((b,), jnp.int32, one_chip)).compile()
    logits = compiled.out_info[0]
    assert logits.shape == (b, cfg.padded_vocab)


_PLUMBING = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}
_INSTR = re.compile(r"\s+(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _pool_instructions(hlo: str, pool_shape):
    """(computation, opcode, op_name) of every instruction of the optimized
    HLO whose array has the stacked pool's shape or one layer's, unit
    dimensions aside, other than tuple plumbing."""
    shapes = {tuple(d for d in pool_shape if d != 1),
              tuple(d for d in pool_shape[1:] if d != 1)}
    found, comp = [], None
    for line in hlo.splitlines():
        if line and not line[0].isspace():
            comp = "ENTRY" if line.startswith("ENTRY") else line.split()[0]
            continue
        m = _INSTR.match(line)
        if not m or m.group(2) in _PLUMBING:
            continue
        dims = tuple(int(d) for d in m.group(1).split(",") if d and d != "1")
        if dims in shapes:
            op = re.search(r'op_name="([^"]*)"', line)
            found.append((comp, m.group(2), op.group(1) if op else ""))
    return found


# Qwen2-1.5B's and DeepSeek-V2-Lite's widths are the serve cells'.
# smollm-135m's pools (D 64 and 512 pages) take a default device layout
# with pages minor, which the page gather cannot read in place, so there
# the program relayouts each pool once into the layer loop and once out of
# it, and nowhere else.  DeepSeek's latent pools (one per segment: the
# dense layer's and the MoE layers') keep rows minor at the padded width.
@pytest.mark.parametrize("arch,relayouts", [("smollm-135m", 4),
                                            ("qwen2-1.5b", 0),
                                            ("deepseek-v2-lite", 0)])
def test_engine_decode_writes_pools_in_place_on_v5e(one_chip, arch,
                                                    relayouts):
    """The engine's decode program (caches donated) gets the stacked KV
    or latent pools back in the buffers they came in, and no instruction
    copies, slices or rebuilds a pool: inside the layer loop a pool-shaped
    array is only ever the in-place write of the step's tokens."""
    cfg = get_config(arch).replace(head_pad_multiple=1)
    if arch == "deepseek-v2-lite":       # the benchmark's one-chip depth
        cfg = cfg.replace(num_layers=5)
    ecfg = EngineConfig(max_batch=8, page_size=16, num_pages=512,
                        max_pages_per_seq=32)
    params = jax.eval_shape(partial(T.init_params, cfg), jax.random.key(0))
    engine = SimpleNamespace(cfg=cfg, ecfg=ecfg, plan=cfg.decoder_plan())
    caches = jax.eval_shape(partial(ServeEngine._init_caches, engine))
    b = ecfg.max_batch
    compiled = decode_program(cfg).lower(
        _on(params, one_chip), _on(caches, one_chip),
        _sds((b,), jnp.int32, one_chip),
        _sds((b,), jnp.int32, one_chip)).compile()
    pools = [c[k] for c in caches for k in c if k.endswith("_pool")]
    pool_bytes = sum(p.size * p.dtype.itemsize for p in pools)
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    for shape in {p.shape for p in pools}:
        found = _pool_instructions(compiled.as_text(), shape)
        writes = [f for f in found if f[1] in ("scatter", "fusion")
                  and f[2].endswith("/scatter")]
        assert writes, "the tokens' write into the pools was not found"
        copies = [f for f in found if f[:2] == ("ENTRY", "copy")]
        assert len(copies) == relayouts, copies
        assert not [f for f in found if f not in writes + copies]


def test_engine_decode_reads_routed_experts_in_place_on_v5e(one_chip,
                                                          monkeypatch):
    """DeepSeek-V2-Lite's decode program, traced as for the chip (Pallas
    kernels), hands the grouped matmul each MoE segment's stacked experts
    whole: the stacks enter the layer loop unsliced and are read there
    through their [count x E, k, n] view, and no instruction has one
    layer's expert-stack shape, which a copy of a layer's experts out of
    the stack would have."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("deepseek-v2-lite").replace(head_pad_multiple=1,
                                                  num_layers=5)
    ecfg = EngineConfig(max_batch=32, page_size=16, num_pages=512,
                        max_pages_per_seq=32)
    params = jax.eval_shape(partial(T.init_params, cfg, dtype=jnp.bfloat16),
                            jax.random.key(0))
    engine = SimpleNamespace(cfg=cfg, ecfg=ecfg, plan=cfg.decoder_plan())
    caches = jax.eval_shape(partial(ServeEngine._init_caches, engine))
    b = ecfg.max_batch
    hlo = decode_program(cfg).lower(
        _on(params, one_chip), _on(caches, one_chip),
        _sds((b,), jnp.int32, one_chip),
        _sds((b,), jnp.int32, one_chip)).compile().as_text()
    moe = params["segments"][1]["moe"]
    stacks = {moe[k].shape for k in ("w_gate", "w_up", "w_down")}
    assert stacks == {(4, 64, 2048, 1408), (4, 64, 1408, 2048)}
    for shape in stacks:
        assert not _pool_instructions(hlo, shape)
        view = "[%d,%s]" % (shape[0] * shape[1], ",".join(map(str, shape[2:])))
        loop_views = re.findall(r"\n\s+%\S+ = bf16" + re.escape(view)
                                + r"\S* bitcast\(", hlo.split("\nENTRY")[0])
        assert loop_views, f"no {view} view of the stacked experts in the loop"


def test_engine_prefill_compiles_for_v5e(one_chip, smollm):
    cfg, _, params, _ = smollm
    bucket = 64
    cache = _on(jax.eval_shape(partial(T.init_cache, cfg, 1, bucket + 8)),
                one_chip)
    tokens = {"tokens": _sds((1, bucket), jnp.int32, one_chip)}
    compiled = jax.jit(partial(T.prefill, cfg)).lower(
        params, tokens, cache).compile()
    assert compiled.out_info[0].shape == (1, cfg.d_model)


@pytest.mark.parametrize("op", ["page_gather", "page_scatter"])
def test_chip_smoke_kernel_check_sees_the_kernel(one_chip, op):
    """``chip_smoke.py``'s proof that a phase ran the Pallas kernel accepts
    the ``impl="pallas"`` dispatch and refuses the jnp reference."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    fn = getattr(page_ops, op)
    args = (_sds((64, 1024, 128), jnp.float32, one_chip),
            _sds((16,), jnp.int32, one_chip))
    if op == "page_scatter":
        args += (_sds((16, 1024, 128), jnp.float32, one_chip),)
    chip_smoke._check_kernel_lowered(fn, "pallas", *args)
    with pytest.raises(RuntimeError, match="no tpu_custom_call"):
        chip_smoke._check_kernel_lowered(fn, "ref", *args)
