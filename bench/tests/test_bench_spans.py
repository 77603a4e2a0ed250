"""The per-layer metrics read from the program's own spans, at smoke size on
the CPU: each appears in a traced run of its cell and in no other cell,
the readers agree with the counts of the cell's own run, and a program
that records no spans leaves them out of the result line."""

import json
import sys
from types import SimpleNamespace

import pytest

import cells
import harness

SPEC = json.loads((cells.BENCH.parent / "BENCHMARK.json").read_text())
SPAN_METRICS = {
    "smollm-135m.train_ooc": {"pager.fault_wait_s", "pager.fill_s",
                              "pager.writeback_s", "ooc.grad_s",
                              "ooc.transfers_per_step"},
    "qwen2-1.5b.decode_long": {"engine.host_ms.serve"},
    "qwen2-1.5b.chat_prefix": {"engine.admit_ms.chat",
                               "engine.prefill_redundant_share.chat"},
}
ALL = set().union(*SPAN_METRICS.values())
SECONDS = {"smollm-135m.train_ooc": 0.5, "qwen2-1.5b.chat_prefix": 2.0}


@pytest.fixture(scope="module")
def traced():
    """A traced smoke run of each cell: its result line, the context its
    metric readers saw, and the program's span records of its window."""
    from repro.telemetry import spans
    seen = []
    make = harness.MetricContext

    def keep(**kw):
        seen.append(make(**kw))
        return seen[-1]

    runs = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "MetricContext", keep)
        for cell in SPAN_METRICS:
            out = cells.run(cell, seconds=SECONDS.get(cell, 0.3), trace=True)
            runs[cell] = (out, seen[-1], spans.session())
    return runs


def test_benchmark_lists_each_span_metric_for_its_cell():
    listed = {m["name"]: m["workloads"] for m in SPEC["per_layer"]
              if m["name"] in ALL}
    assert listed == {name: [cell] for cell, names in SPAN_METRICS.items()
                      for name in names}


@pytest.mark.parametrize("cell", sorted(SPAN_METRICS))
def test_span_metrics_appear_in_their_cell_only(traced, cell):
    out, _, recs = traced[cell]
    assert out["correct"], out["checks"]
    assert recs
    assert set(out["metrics"]) & ALL == SPAN_METRICS[cell]
    assert all(out["metrics"][m]["value"] >= 0 for m in SPAN_METRICS[cell])


def test_prefill_redundant_share_is_the_held_share(traced):
    out, ctx, _ = traced["qwen2-1.5b.chat_prefix"]
    prefills = ctx.data["prefills"]            # (new tokens, tokens held)
    held = sum(h for _, h in prefills)
    assert held > 0
    share = 100.0 * held / sum(n + h for n, h in prefills)
    got = out["metrics"]["engine.prefill_redundant_share.chat"]["value"]
    assert got == pytest.approx(share)


def test_grad_and_sweep_fit_in_the_traced_step(traced):
    out, ctx, recs = traced["smollm-135m.train_ooc"]
    steps = [r.seconds for r in recs if r.name == "umap.ooc.step"]
    assert len(steps) == ctx.data["steps"] > 0
    m = out["metrics"]
    assert m["ooc.grad_s"]["value"] + m["ooc.sweep_s"]["value"] \
        <= sum(steps) / len(steps)


def _rec(name, sid, parent, start, end, **counts):
    from repro.telemetry.spans import Record
    return Record(name, start, end, sid, parent, None, counts, 1)


def test_host_ms_is_the_step_less_decode_and_admissions():
    """A decoding step's host time is its span less its decode; the
    admission's spans inside it are host work (dispatch), so they stay in,
    and a step that decoded nothing is left out."""
    ms = 1_000_000
    recs = [_rec("umap.engine.step", 1, None, 0, 50 * ms),
            _rec("umap.engine.admit", 2, 1, 0, 12 * ms),
            _rec("umap.engine.prefill", 3, 2, 1 * ms, 11 * ms),
            _rec("umap.engine.install", 4, 3, 8 * ms, 9 * ms),
            _rec("umap.engine.decode", 5, 1, 15 * ms, 49 * ms),
            _rec("umap.engine.step", 6, None, 60 * ms, 100 * ms),
            _rec("umap.engine.decode", 7, 6, 62 * ms, 98 * ms),
            _rec("umap.engine.step", 8, None, 110 * ms, 111 * ms)]
    reader = harness.load_module(cells.BENCH, "metrics", "engine.host_ms.serve")
    ctx = SimpleNamespace(data={})
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("program_spans.window", lambda: recs)
        # (50 - 34) and (40 - 36)
        assert reader.read(ctx) == pytest.approx((16 + 4) / 2)


def test_admit_ms_adds_the_admissions_device_time():
    """Milliseconds per admission: the host's prefill spans plus the
    window's prefill and install-scatter programs, over the admissions."""
    ms = 1_000_000
    recs = [_rec("umap.engine.prefill", 1, None, 0, 4 * ms),
            _rec("umap.engine.prefill", 2, None, 10 * ms, 16 * ms)]
    modules = {"jit_prefill(3)": {"count": 2, "seconds": 0.070},
               "jit_scatter(9)": {"count": 4, "seconds": 0.040},
               "jit_decode_step(5)": {"count": 30, "seconds": 1.5}}
    import trace_reduce
    ctx = SimpleNamespace(data={}, trace=trace_reduce.Summary(
        window_s=2.0, busy_s=1.61, modules=modules))
    reader = harness.load_module(cells.BENCH, "metrics", "engine.admit_ms.chat")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("program_spans.window", lambda: recs)
        assert reader.read(ctx) == pytest.approx((4 + 6 + 70 + 40) / 2)


@pytest.mark.parametrize("metric", sorted(ALL))
def test_a_program_without_spans_leaves_the_metric_out(metric, monkeypatch):
    import repro.telemetry
    from repro.telemetry import spans
    reader = harness.load_module(cells.BENCH, "metrics", metric)
    ctx = SimpleNamespace(data={"steps": 2}, run=None, trace=None)
    monkeypatch.setattr(spans, "STORE", spans.SpanStore())
    assert reader.read(ctx) is None             # nothing recorded
    monkeypatch.delattr(repro.telemetry, "spans")
    monkeypatch.setitem(sys.modules, "repro.telemetry.spans", None)
    assert reader.read(ctx) is None             # no span facility at all
