"""Trace reduction: synthetic planes with known answers, and a small trace
recorded on a TPU v5e kept in ``bench/testdata``."""

import sys
from collections import namedtuple
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce as tr  # noqa: E402

Plane = namedtuple("Plane", "name lines")
Line = namedtuple("Line", "name events")
Ev = namedtuple("Ev", "name start_ns duration_ns")

MS = 1_000_000


def _planes():
    host = Plane("/host:CPU", [Line("python3", [
        Ev("bench.window", 0, 100 * MS),
        Ev("bench.step", 0, 50 * MS),
        Ev("bench.step", 50 * MS, 40 * MS),
        Ev("PjitFunction(argmax)", 30 * MS, 20 * MS),
        Ev("bench.wait", 85 * MS, 15 * MS),
    ])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_step(1)", 10 * MS, 20 * MS),
                             Ev("jit_step(1)", 60 * MS, 20 * MS),
                             Ev("jit_other(2)", 120 * MS, 5 * MS)]),
        Line("XLA Ops", [
            Ev("%while.1 = f32[8]{0} while(...)", 10 * MS, 20 * MS),
            Ev("%fusion.3 = bf16[4,8]{1,0:T(8,128)} fusion(...)", 10 * MS, 15 * MS),
            Ev("%copy.2 = f32[8]{0} copy(...)", 25 * MS, 5 * MS),
            Ev("%fusion.3 = bf16[4,8]{1,0:T(8,128)} fusion(...)", 60 * MS, 20 * MS),
        ]),
    ])
    return [host, dev, Plane("/host:metadata", [])]


def test_synthetic_window_busy_programs_and_gaps():
    s = tr.reduce_planes(_planes(), "bench.window")
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.04)            # [10,30] and [60,80] ms
    assert s.modules == {"jit_step(1)": {"count": 2, "seconds": pytest.approx(0.04)}}
    # the enclosing while loop is not counted beside its body
    assert s.ops == {"jit_step:fusion bf16[4,8]": pytest.approx(0.035),
                     "jit_step:copy f32[8]": pytest.approx(0.005)}
    assert s.spans == {"bench.window": 1, "bench.step": 2, "bench.wait": 1}
    gaps = dict(s.gaps)
    assert gaps["bench.wait"] == pytest.approx(0.02)   # [80, 100] ms
    assert gaps["bench.step"] == pytest.approx(0.01)   # [0, 10] ms
    assert gaps["bench.step > PjitFunction(argmax)"] == pytest.approx(0.03)  # [30, 60]
    b = s.breakdown()
    assert b["device_ops"][0] == ["jit_step:fusion bf16[4,8]", pytest.approx(0.035)]
    assert len(b["idle_gaps"]) == 3


def test_program_found_by_name_or_by_calls():
    s = tr.Summary(window_s=1.0, busy_s=0.5, modules={
        "jit__unknown(11)": {"count": 100, "seconds": 0.4},
        "jit__unknown(12)": {"count": 3, "seconds": 0.05},
        "jit__unknown(13)": {"count": 2, "seconds": 0.03},
        "jit__argmax(7)": {"count": 100, "seconds": 0.001}})
    dec = s.program("decode_step", dispatches=100)
    assert dec["name"] == "jit__unknown(11)" and dec["seconds"] == 0.4
    assert s.others_like(dec["name"]) == {"count": 5, "seconds": pytest.approx(0.08)}
    assert s.program("argmax")["count"] == 100
    assert s.program("decode_step") is None


def test_recorded_tpu_trace():
    s = tr.reduce(BENCH / "testdata", "bench.step")
    assert s.spans == {"bench.step": 1}
    assert s.window_s == pytest.approx(0.00211842)
    assert list(s.modules) == ["jit_page_scatter(1329917740033021845)"]
    assert s.ops == {"jit_page_scatter:page_scatter f32[16,1024,128]":
                     pytest.approx(7.123e-06)}
    assert s.busy_s == pytest.approx(7.123e-06)
