"""Out-of-core training (DESIGN.md §18): the differential suite.

The load-bearing claim: a trainer whose params + AdamW moments live
behind UMap regions — streamed through a page buffer 2-4x smaller than
the state — produces BITWISE identical params, moments, and losses to
the plain resident-buffer trainer, across page sizes and buffer sizes.
The decomposed update (``update_scalars`` once per step +
``adamw_elementwise`` per page chunk) is what makes that equality exact
rather than approximate; these tests are the proof the bench's
``step_time_ratio`` claim stands on.

Also here: the zero-staging-copy lease invariant, the adaptive
classifier earning the ``sequential`` verdict the advise path is given
for free, chaos-injected faults (transient + hard outage) surfacing as
``OSError`` or completing bitwise-exact — never silent corruption — the
§18.4 writer-exclusion regression (async checkpoint vs in-flight write
leases), and elastic restore onto a different mesh through the batched
store path.
"""

import threading
import time

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import ChaosStore, HostArrayStore
from repro.train.ooc import OOCTrainer, OOCTrainerConfig
from repro.train.paged_state import (
    interleave_moments,
    pack_tree,
    split_moments,
)
from repro.train.train_step import TrainConfig

PAGE = 4096
B, S = 2, 16
STEPS = 3


def _model_cfg() -> ModelConfig:
    return ModelConfig(name="tiny", family="dense", num_layers=2,
                       d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
                       d_ff=128, vocab_size=256)


def _batches(n=STEPS, seed=0):
    cfg = _model_cfg()
    rng = np.random.default_rng(seed)
    return [{"tokens": rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int64)
             .astype(np.int32),
             "labels": rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int64)
             .astype(np.int32)}
            for _ in range(n)]


def _geom(page_size):
    """(params_pages, mv_pages, largest_leaf_pages) for the tiny model."""
    from repro.models import transformer as T

    params = jax.tree.map(np.asarray,
                          T.init_params(_model_cfg(), jax.random.key(1)))
    _, specs, _ = pack_tree(params, page_size)
    mv = jax.tree.map(lambda p: np.zeros(2 * p.size, np.float32), params)
    _, mv_specs, _ = pack_tree(mv, page_size)
    return (sum(s["npages"] for s in specs),
            sum(s["npages"] for s in mv_specs),
            max(s["npages"] for s in specs))


def _paged_kw(page_size, oversub):
    """Buffer sizing for ~``oversub``x state oversubscription."""
    pt, mt, largest = _geom(page_size)
    budget = (pt + mt) // oversub
    p_slots = max(2 * largest, pt // oversub)
    return dict(params_buffer_pages=p_slots,
                moments_buffer_pages=max(8, budget - p_slots))


def _make(paged, page_size=PAGE, ocfg_kw=None, **trainer_kw):
    ocfg = OOCTrainerConfig(page_size=page_size, **(ocfg_kw or {}))
    return OOCTrainer(_model_cfg(), TrainConfig(), ocfg,
                      rng=jax.random.key(1), paged=paged, **trainer_kw)


def _assert_state_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(x, y)


@pytest.fixture(scope="module")
def resident_ref():
    """(state_dict, losses) from the plain resident trainer — the oracle."""
    tr = _make(paged=False)
    losses = [float(tr.step(b)["loss"]) for b in _batches()]
    state = tr.state_dict()
    tr.close()
    return state, losses


# ------------------------------------------------------------- differential


class TestDifferential:
    """Paged == resident, bitwise, across buffer and page geometries."""

    @pytest.mark.parametrize("page_size,oversub", [
        (PAGE, 1),          # pager in the loop, but nothing ever evicted
        (PAGE, 2),
        (PAGE, 4),          # the headline: 4x oversubscription
        (2 * PAGE, 4),      # different page size => different chunking
    ])
    def test_bitwise_equivalence(self, resident_ref, page_size, oversub):
        ref_state, ref_losses = resident_ref
        kw = (_paged_kw(page_size, oversub) if oversub > 1 else {})
        tr = _make(paged=True, page_size=page_size, ocfg_kw=kw)
        try:
            if oversub > 1:
                assert tr.oversubscription() >= oversub * 0.95
            losses = [float(tr.step(b)["loss"]) for b in _batches()]
            assert losses == ref_losses
            _assert_state_equal(tr.state_dict(), ref_state)
            # Zero staging copies: every lease on the training path was a
            # direct page-buffer view (DESIGN.md §13).
            assert tr.staging_copies == 0
        finally:
            tr.close()

    def test_explicit_chunk_size_is_bitwise_invariant(self, resident_ref):
        """Forcing a tiny sweep chunk must not change a single bit —
        the per-page decomposition claim (§18.2) at its sharpest."""
        ref_state, ref_losses = resident_ref
        kw = dict(_paged_kw(PAGE, 2), sweep_chunk_pages=1)
        tr = _make(paged=True, ocfg_kw=kw)
        try:
            losses = [float(tr.step(b)["loss"]) for b in _batches()]
            assert losses == ref_losses
            _assert_state_equal(tr.state_dict(), ref_state)
        finally:
            tr.close()


# ------------------------------------------------------- access pattern


class TestSequentialWitness:
    def test_classifier_earns_sequential_on_moment_sweep(self, resident_ref):
        """With ``adaptive=True`` the moments region is NOT advised; the
        online classifier must still settle on ``sequential`` from the
        sweep's strictly ascending lease runs — application knowledge
        and learned behavior agreeing (paper §3.6)."""
        ref_state, _ = resident_ref
        kw = dict(_paged_kw(PAGE, 4), adaptive=True)
        tr = _make(paged=True, ocfg_kw=kw)
        try:
            for b in _batches():
                tr.step(b)
            # Adaptive retuning changes prefetch, never bytes.
            _assert_state_equal(tr.state_dict(), ref_state)
            # The classifier samples DEMAND faults only, so when prefetch
            # absorbs most of a short run it may still be in warmup after
            # three steps — keep sweeping (strictly ascending) until it
            # has the evidence; it must then call the phase sequential.
            mv_region = tr.opt.region
            snap = None
            for b in _batches(n=6, seed=99):
                snap = mv_region.service.pattern_snapshot(
                    mv_region.region_id)
                assert snap is not None
                if snap["phase"] == "sequential":
                    break
                tr.step(b)
            else:
                snap = mv_region.service.pattern_snapshot(
                    mv_region.region_id)
            assert snap["phase"] == "sequential", snap
        finally:
            tr.close()


# --------------------------------------------------------------- chaos


class TestChaosTraining:
    def test_transient_faults_retry_bitwise(self, resident_ref):
        """Deterministically injected read+write faults on the moments
        store: steps complete bitwise-exact through the stash-and-retry
        path, quarantined write-backs drain, nothing corrupts."""
        ref_state, ref_losses = resident_ref
        chaos = []

        def factory(buf):
            chaos.append(ChaosStore(HostArrayStore(buf), seed=5))
            return chaos[0]

        kw = dict(_paged_kw(PAGE, 2), max_step_retries=8)
        tr = _make(paged=True, ocfg_kw=kw, moments_store_factory=factory)
        try:
            losses = []
            for i, b in enumerate(_batches()):
                if i == 1:
                    chaos[0].fail_next("read", 3)
                    chaos[0].fail_next("write", 2)
                losses.append(float(tr.step(b)["loss"]))
            assert chaos[0].injected_read_errors == 3
            assert losses == ref_losses
            assert tr.stats["io_errors"] > 0
            assert tr.stats["step_retries"] > 0
            tr.drain_quarantine()
            _assert_state_equal(tr.state_dict(), ref_state)
        finally:
            tr.close()

    def test_outage_surfaces_oserror_then_resumes(self, resident_ref):
        """A hard outage window: the step raises OSError (never silently
        corrupts), and after revive the SAME step replays bitwise via the
        stashed grads + chunk done-set."""
        ref_state, ref_losses = resident_ref
        chaos = []

        def factory(buf):
            chaos.append(ChaosStore(HostArrayStore(buf), seed=7))
            return chaos[0]

        kw = dict(_paged_kw(PAGE, 2), max_step_retries=2)
        tr = _make(paged=True, ocfg_kw=kw, moments_store_factory=factory)
        try:
            bs = _batches()
            losses = [float(tr.step(bs[0])["loss"])]
            chaos[0].kill()
            with pytest.raises(OSError):
                tr.step(bs[1])
            assert tr.stats["io_errors"] > 0
            assert tr.step_no == 1          # the failed step did not count
            chaos[0].revive()
            tr.drain_quarantine()
            losses.append(float(tr.step(bs[1])["loss"]))
            losses.append(float(tr.step(bs[2])["loss"]))
            assert losses == ref_losses
            _assert_state_equal(tr.state_dict(), ref_state)
        finally:
            tr.close()


# ------------------------------------------------- §18.4 writer exclusion


class TestAsyncCheckpointVsWriteLeases:
    def test_save_blocks_on_inflight_write_lease(self, tmp_path):
        """Regression: ``save_async`` during an in-flight ``lease_run``
        update must block until the write lease releases — the snapshot
        sees all-of-the-update or none-of-it, never torn bytes."""
        kw = dict(_paged_kw(PAGE, 2), ckpt_dir=str(tmp_path))
        tr = _make(paged=True, ocfg_kw=kw)
        try:
            tr.step(_batches(1)[0])
            region = tr.opt.region
            run = region.lease_run(0, 2, write=True)
            # Torn state: page 0 mutated, page 1 not yet.
            run[0].view[:] = 0xAB
            saved = threading.Event()

            def save():
                tr.save_checkpoint()        # snapshot_tree blocks in here
                saved.set()

            t = threading.Thread(target=save, daemon=True)
            t.start()
            assert not saved.wait(0.3), \
                "snapshot completed while a write lease was held"
            run[1].view[:] = 0xAB           # finish the update
            run.release()
            assert saved.wait(5.0), "snapshot never unblocked"
            t.join()
            assert region.stats()["lease_excl_waits"] >= 1
            tr.ckptr.flush()

            # The published checkpoint must hold the COMPLETE update.
            tr2 = _make(paged=True, ocfg_kw=kw)
            try:
                assert tr2.try_resume()
                m0 = jax.tree_util.tree_leaves(
                    tr2.opt.snapshot_tree()["m"])[0]
                page = np.asarray(m0).reshape(-1)[:2 * PAGE // 8]
                expect = np.frombuffer(
                    bytes([0xAB]) * (2 * PAGE), np.float32)[0::2]
                np.testing.assert_array_equal(page, expect[:page.size])
            finally:
                tr2.close()
        finally:
            tr.close()


# ----------------------------------------------------- elastic restore


class TestElasticRestore:
    def test_restore_onto_different_mesh_batched(self):
        """Checkpoint from the paged trainer, restore through ONE batched
        store read, re-placed on a different logical mesh — tree equal."""
        from repro.ckpt.checkpoint import save_tree_to_store
        from repro.distributed.elastic import restore_train_state_elastic

        tr = _make(paged=True, ocfg_kw=_paged_kw(PAGE, 2))
        try:
            for b in _batches(2):
                tr.step(b)
            state = tr.state_dict()
        finally:
            tr.close()

        nbytes = sum(np.asarray(a).nbytes
                     for a in jax.tree_util.tree_leaves(state))
        store = HostArrayStore(np.zeros(nbytes + PAGE, np.uint8))
        manifest = save_tree_to_store(store, state)
        store.reset_stats()

        mesh = jax.make_mesh((1,), ("model",))
        like = jax.tree.map(lambda a: np.zeros_like(np.asarray(a)), state)
        restored, report = restore_train_state_elastic(
            _model_cfg(), mesh, store, manifest, like)
        assert report.devices == 1
        assert store.num_reads == 1, "restore must be ONE batched read"
        # The store path round-trips scalar leaves as shape-(1,) arrays;
        # compare step by value, the array trees bitwise.
        assert int(np.asarray(restored["step"]).reshape(-1)[0]) \
            == int(state["step"])
        _assert_state_equal(
            {k: v for k, v in restored.items() if k != "step"},
            {k: v for k, v in state.items() if k != "step"})

        # Round-trip: the restored tree loads back into a fresh paged
        # trainer and reproduces the exact state.
        tr2 = _make(paged=True, ocfg_kw=_paged_kw(PAGE, 2))
        try:
            tr2.load_state_dict(jax.tree.map(np.asarray, restored))
            assert tr2.step_no == int(np.asarray(state["step"]))
            _assert_state_equal(tr2.state_dict(), state)
        finally:
            tr2.close()


# --------------------------------------------------------- checkpointing


class TestCheckpointResume:
    def test_paged_save_resume_roundtrip(self, tmp_path):
        kw = dict(_paged_kw(PAGE, 2), ckpt_dir=str(tmp_path))
        tr = _make(paged=True, ocfg_kw=kw)
        try:
            for b in _batches(2):
                tr.step(b)
            tr.save_checkpoint()
            tr.ckptr.flush()
            state = tr.state_dict()
        finally:
            tr.close()

        tr2 = _make(paged=True, ocfg_kw=kw)
        try:
            assert tr2.try_resume()
            assert tr2.step_no == 2
            _assert_state_equal(tr2.state_dict(), state)
        finally:
            tr2.close()


# ------------------------------------------------------------- telemetry


class TestTrainCollector:
    def test_collects_counters_and_gauges(self):
        from repro.telemetry.collectors import TrainCollector

        tr = _make(paged=True, ocfg_kw=_paged_kw(PAGE, 2))
        try:
            tr.step(_batches(1)[0])
            fams = TrainCollector(trainer=tr).collect()
            by_name = {f.name: f for f in fams}
            assert by_name["umap_train_steps_total"].samples[0][2] == 1
            assert by_name["umap_train_staging_copies_total"].samples[0][2] \
                == 0
            assert by_name["umap_train_oversubscription_ratio"] \
                .samples[0][2] == pytest.approx(tr.oversubscription())
            assert by_name["umap_train_sweep_pages_total"].samples[0][2] > 0
        finally:
            tr.close()

    def test_transfer_counters_match_the_step_spans(self, tmp_path):
        """The trainer counts every host<->device copy of a step in its
        stats, which the collector exports; under a profiler capture the
        step records its grad phase and sweep inside it, and transfer spans
        carrying the same counts."""
        from repro.telemetry import spans
        from repro.telemetry.collectors import TrainCollector

        tr = _make(paged=True, ocfg_kw=_paged_kw(PAGE, 2))
        try:
            keys = ("h2d_copies", "h2d_bytes", "d2h_copies", "d2h_bytes")
            before = {k: tr.stats[k] for k in keys}
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                tr.step(_batches(1)[0])
            finally:
                jax.profiler.stop_trace()
            recs = spans.session()
            by_id = {r.span_id: r for r in recs}
            (step,) = [r for r in recs if r.name == "umap.ooc.step"]
            for name in ("umap.ooc.grad", "umap.ooc.sweep"):
                (r,) = [r for r in recs if r.name == name]
                assert r.parent_id == step.span_id
            assert {by_id[r.parent_id].name for r in recs
                    if r.name == "umap.transfer.d2h"} \
                == {"umap.ooc.grad", "umap.ooc.page_update"}
            for d in ("h2d", "d2h"):
                mine = [r.counts for r in recs
                        if r.name == f"umap.transfer.{d}"]
                for what in ("copies", "bytes"):
                    k = f"{d}_{what}"
                    assert tr.stats[k] - before[k] \
                        == sum(c[what] for c in mine) > 0
            # the parameter source counts its own page fetches
            assert 0 < tr.source.h2d_copies < tr.stats["h2d_copies"]
            by_name = {f.name: f for f in TrainCollector(trainer=tr).collect()}
            for k in keys:
                assert by_name[f"umap_train_{k}_total"].samples[0][2] \
                    == tr.stats[k]
            # a sweep page moves its params, grads and moments in and its
            # results out: at least 5 copies per parameter page
            pages = sum(s["npages"] for s in tr._p_specs)
            assert tr.stats["h2d_copies"] + tr.stats["d2h_copies"] \
                >= 5 * pages
        finally:
            tr.close()

    def test_empty_without_trainer(self):
        from repro.telemetry.collectors import TrainCollector

        assert TrainCollector().collect() == []


# ----------------------------------------------------- layout round-trips


class TestPackedLayout:
    """Deterministic spot-checks; the hypothesis sweep of the same
    invariants lives in test_train_ooc_property.py."""

    def test_pack_tree_roundtrip(self):
        rng = np.random.default_rng(3)
        page = 256
        tree = {f"l{i}": rng.standard_normal(n).astype(np.float32)
                for i, n in enumerate((1, 63, 64, 65, 300))}
        buf, specs, _ = pack_tree(tree, page)
        assert buf.nbytes % page == 0
        leaves = jax.tree_util.tree_leaves(tree)
        for leaf, spec in zip(leaves, specs):
            lo = spec["first_page"] * page
            got = buf[lo:lo + spec["nbytes"]].view(np.float32)
            np.testing.assert_array_equal(got, leaf.reshape(-1))
            pad = buf[lo + spec["nbytes"]:lo + spec["npages"] * page]
            assert not pad.any(), "inter-leaf padding must be zero"

    def test_interleave_split_roundtrip(self):
        rng = np.random.default_rng(4)
        shape = (7, 5)
        m = {"w": rng.standard_normal(shape).astype(np.float32)}
        v = {"w": rng.standard_normal(shape).astype(np.float32)}
        mv = interleave_moments(m, v)["w"]
        # Element-interleaved: one ascending scan covers both moments.
        np.testing.assert_array_equal(mv[0::2], m["w"].reshape(-1))
        np.testing.assert_array_equal(mv[1::2], v["w"].reshape(-1))
        m2, v2 = split_moments(mv, shape)
        np.testing.assert_array_equal(m2, m["w"])
        np.testing.assert_array_equal(v2, v["w"])


# ------------------------------------- gather/scatter donation regression


class TestGatherCompletesUnderLock:
    """``page_scatter`` installs layers into the device pool by donating
    the pool buffer (in-place write).  A layer gather still *executing*
    when the next layer's scatter dispatches therefore reads
    half-overwritten pages — the lock in ``RegionLayerSource`` orders
    dispatch, not execution.  The fix runs every gather to completion
    before the lock is released; this pins that contract (the failure it
    prevents is a ~25%-rate bitwise divergence of the whole training
    state at bench geometry, seeded by one torn params page)."""

    def test_gather_result_ready_on_return(self, monkeypatch):
        import repro.serve.weight_pager as wp
        from repro.core.config import UMapConfig
        from repro.core.region import umap, uunmap

        page = 512 * 1024           # big enough that an un-synced gather
        rng = np.random.default_rng(5)   # could not finish by accident
        tree = {"a": rng.standard_normal(page).astype(np.float32),
                "b": rng.standard_normal(page // 2).astype(np.float32),
                "c": rng.standard_normal(page).astype(np.float32)}
        buf, specs, _ = pack_tree(tree, page)
        reg = umap(HostArrayStore(buf),
                   config=UMapConfig(page_size=page, buffer_size=buf.nbytes,
                                     max_lease_run=8))
        try:
            src = wp.RegionLayerSource(reg, specs)
            gathered = []
            orig = wp.page_gather

            def capture(pool, ids, **kw):
                out = orig(pool, ids, **kw)
                gathered.append(out)
                return out

            monkeypatch.setattr(wp, "page_gather", capture)
            leaves = jax.tree_util.tree_leaves(tree)
            for _ in range(2):          # fetch-install pass + cached pass
                for i, leaf in enumerate(leaves):
                    got = src[i]
                    assert gathered[-1].is_ready(), \
                        "pool gather must complete before __getitem__ " \
                        "returns (donated scatter would tear it)"
                    np.testing.assert_array_equal(np.asarray(got),
                                                  np.asarray(leaf))
                src.invalidate()
            assert len(gathered) == 2 * len(leaves)
        finally:
            uunmap(reg)
