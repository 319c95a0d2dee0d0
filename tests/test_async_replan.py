"""Async surface replanning tests: stale-while-revalidate semantics.

Everything here is deterministic — rebuild jobs run on a
:class:`ManualExecutor` only when the test says so, so "a rebuild is in
flight" is an exact program state (no sleeps, no races).
"""

import numpy as np
import pytest
from dataclasses import replace

from repro.core.adaptive import AdaptiveSplitManager, fleet_managers
from repro.core.async_replan import (
    ManualExecutor,
    SurfaceRebuilder,
    cpu_process_pool,
    recentered_axes,
)
from repro.core.profiles import ESP_NOW, PROTOCOLS, paper_cost_model
from repro.core.surface import DegradationSurface

GRID = {"pt_scale": (1.0, 4.0, 16.0), "loss_p": (0.0, 0.1)}
NBYTES = 5488


def _mgr(executor, n_devices=2, **kw):
    return AdaptiveSplitManager(
        cost_model=paper_cost_model("mobilenet_v2", "esp_now"),
        protocols=dict(PROTOCOLS), n_devices=n_devices,
        solver="optimal_dp", surface_grid=GRID, async_rebuild=executor, **kw)


def _drive(mgr, factor, steps, protocol="esp_now"):
    lat = factor * ESP_NOW.transmission_latency_s(NBYTES)
    for _ in range(steps):
        mgr.observe(protocol, NBYTES, lat)


def _settle_and_adopt(mgr, ex, factor, max_cycles=6):
    """Drive the drifted estimate to its EWMA fixed point, then run
    rebuild cycles until the (settled) state is covered by the adopted
    surface. Returns the number of cycles used."""
    _drive(mgr, factor, 80)  # EWMA converges; rebuilds queue meanwhile
    for cycle in range(1, max_cycles + 1):
        ex.run_all()
        _drive(mgr, factor, 2)  # poll: adopt / launch the re-centered build
        est = mgr.estimators["esp_now"]
        if mgr.surface.in_envelope("esp_now", est.packet_time_estimate,
                                   est.loss_estimate):
            return cycle
    raise AssertionError("drifted state never covered by a rebuilt surface")


def _assert_node_identical(a: DegradationSurface, b: DegradationSurface):
    assert sorted(a.protocols) == sorted(b.protocols)
    for name in a.protocols:
        pa, pb = a.protocols[name], b.protocols[name]
        assert pa.packet_time_s == pb.packet_time_s, name
        assert pa.loss_p == pb.loss_p, name
        assert np.array_equal(pa.splits, pb.splits), name
        assert np.array_equal(pa.chunk_bytes, pb.chunk_bytes), name
        assert np.array_equal(pa.latency_s, pb.latency_s), name
        assert np.array_equal(pa.runner_splits, pb.runner_splits), name
        assert np.array_equal(pa.runner_latency_s, pb.runner_latency_s), name


class TestManualExecutor:
    def test_fifo_and_counts(self):
        ex = ManualExecutor()
        order = []
        ex.submit(lambda: order.append("a"))
        ex.submit(lambda: order.append("b"))
        assert ex.pending() == 2 and ex.submitted == 2 and ex.executed == 0
        assert ex.run_next()
        assert order == ["a"]
        assert ex.run_all() == 1
        assert order == ["a", "b"]
        assert not ex.run_next()
        assert ex.executed == 2


class TestRecenteredAxes:
    def test_extends_base_axes_and_covers_state(self):
        base = dict(PROTOCOLS)
        pt = ESP_NOW.packet_time_s() * 300.0
        pts, losses = recentered_axes(
            base, {"esp_now": (pt, 0.25)},
            pt_scale=(1.0, 4.0), loss_p=(0.0, 0.1))
        assert set((1.0, 4.0)) <= set(pts)  # base axes preserved
        assert 300.0 in {round(s, 6) for s in pts}  # ratio * pt_pad 1.0
        assert max(pts) >= 300.0  # headroom above the drifted state
        assert 0.25 in losses and 0.5 in losses  # exact + padded loss

    def test_multiple_state_maps_merge(self):
        pt = ESP_NOW.packet_time_s()
        pts, _ = recentered_axes(
            dict(PROTOCOLS),
            [{"esp_now": (pt * 50, 0.0)}, {"esp_now": (pt * 900, 0.0)}],
            pt_scale=(1.0,), loss_p=(0.0,))
        rounded = {round(s, 6) for s in pts}
        assert 50.0 in rounded and 900.0 in rounded

    def test_pt_pad_must_reach_the_state(self):
        with pytest.raises(ValueError, match="pt_pad"):
            recentered_axes(dict(PROTOCOLS),
                            {"esp_now": (1.0, 0.0)}, pt_pad=(0.25, 0.5))


class TestNoBlocking:
    def test_observe_serves_stale_surface_while_rebuild_in_flight(self):
        """The core stale-while-revalidate contract: out-of-envelope
        observes keep returning (stale decision or bounded exact
        fallback) while the queued rebuild has NOT run."""
        ex = ManualExecutor()
        mgr = _mgr(ex)
        _drive(mgr, 1, 10)
        assert mgr.surface_hits == 10 and ex.pending() == 0
        pre_surface = mgr.surface
        _drive(mgr, 5000, 80)  # way beyond the 16x envelope
        # every observe returned; the rebuild is queued but NOT executed
        assert mgr._step == 90
        assert ex.pending() == 1
        assert mgr.surface is pre_surface  # no swap before the build ran
        assert mgr.stale_serves > 0  # the in-flight window served stale
        # the exact fallback is BOUNDED: it ran only on material moves,
        # not on every out-of-envelope observe
        assert 0 < mgr.exact_fallbacks < 20
        assert mgr.current is not None  # decisions kept flowing

    def test_sync_manager_resolves_every_observe(self):
        """Baseline contrast: without async_rebuild every out-of-envelope
        observe pays the exact re-solve."""
        mgr = AdaptiveSplitManager(
            cost_model=paper_cost_model("mobilenet_v2", "esp_now"),
            protocols=dict(PROTOCOLS), n_devices=2,
            solver="optimal_dp", surface_grid=GRID)
        _drive(mgr, 5000, 30)
        assert mgr.exact_fallbacks == 30


class TestCoalescing:
    def test_n_drift_events_queue_at_most_one_rebuild(self):
        ex = ManualExecutor()
        mgr = _mgr(ex)
        _drive(mgr, 5000, 200)  # 200 drift events
        rb = mgr._rebuilder
        assert rb.builds_started == 1  # ONE build launched...
        assert ex.pending() == 1  # ...and at most one in the executor
        assert len(rb._queued) <= 1  # plus at most ONE coalesced follow-up
        assert rb.requests_coalesced >= 1

    def test_covered_requests_drop_into_inflight(self):
        """A request whose state the in-flight build already covers does
        not queue a follow-up."""
        ex = ManualExecutor()
        mgr = _mgr(ex)
        _drive(mgr, 30, 100)  # settles at ~30x; first build covers to 4x that
        rb = mgr._rebuilder
        assert rb.builds_started == 1
        assert rb._queued == {}  # follow-ups were covered, none queued
        assert rb.requests_coalesced >= 1


class TestAdoption:
    def test_async_adopted_surface_node_identical_to_sync_build(self):
        """Adoption parity: the swapped-in surface must be node-identical
        to the same build_surfaces call made synchronously."""
        ex = ManualExecutor()
        mgr = _mgr(ex)
        _drive(mgr, 30, 100)
        req = mgr._rebuilder.last_request
        ex.run_all()
        _drive(mgr, 30, 1)  # poll adopts
        assert mgr.surface_swaps == 1
        _assert_node_identical(mgr.surface,
                               mgr._rebuilder.build_sync(req)[2])

    def test_adopted_surface_covers_drift_and_restores_o1_path(self):
        ex = ManualExecutor()
        mgr = _mgr(ex)
        cycles = _settle_and_adopt(mgr, ex, 5000)
        assert cycles <= 3 and mgr.surface_swaps >= 1
        h0, f0, s0 = mgr.surface_hits, mgr.exact_fallbacks, mgr.stale_serves
        _drive(mgr, 5000, 40)
        assert mgr.surface_hits == h0 + 40  # O(1) lookups again
        assert mgr.exact_fallbacks == f0 and mgr.stale_serves == s0

    def test_adopted_decision_matches_sync_resolve_manager(self):
        """End state parity with the always-re-solve oracle manager."""
        ex = ManualExecutor()
        mgr = _mgr(ex)
        _settle_and_adopt(mgr, ex, 400)
        oracle = AdaptiveSplitManager(
            cost_model=paper_cost_model("mobilenet_v2", "esp_now"),
            protocols=dict(PROTOCOLS), n_devices=2,
            solver="optimal_dp", surface=None)
        _drive(oracle, 400, 82)
        _drive(oracle, 400, 4)  # same total observe count as mgr
        assert mgr.current.protocol == oracle.current.protocol
        assert mgr.current.splits == oracle.current.splits

    def test_generation_versioning_never_readopts(self):
        """A completed build is adopted exactly once; polling again (or a
        re-posted stale generation) cannot swap the surface twice."""
        ex = ManualExecutor()
        mgr = _mgr(ex)
        _drive(mgr, 30, 100)
        ex.run_all()
        _drive(mgr, 30, 20)
        assert mgr.surface_swaps == 1
        rb = mgr._rebuilder
        assert rb.poll(2) is None  # nothing new
        # a stale generation posted late must NOT be handed out
        stale_surface = mgr.surface
        rb._results[2] = (0, stale_surface)  # older than the adopted gen
        rb._maybe_actionable = True
        assert rb.poll(2) is None
        _drive(mgr, 30, 5)
        assert mgr.surface_swaps == 1

    def test_rebuild_error_surfaces_on_poll(self, monkeypatch):
        ex = ManualExecutor()
        mgr = _mgr(ex)
        monkeypatch.setattr(mgr._rebuilder, "build_sync",
                            lambda req: (_ for _ in ()).throw(
                                RuntimeError("boom")))
        _drive(mgr, 5000, 10)
        ex.run_all()  # the job stashes the error
        with pytest.raises(RuntimeError, match="rebuild failed"):
            _drive(mgr, 5000, 2)

    def test_transient_failure_recovers_with_a_new_rebuild(self):
        """Regression: a failed build must not permanently disable
        revalidation. With the estimate SETTLED (inside the staleness
        tolerance) a transient failure once left the manager serving
        the stale surface forever; now the error resets the staleness
        window so the next drifted observe re-requests, and the retry
        build adopts normally."""
        ex = ManualExecutor()
        mgr = _mgr(ex)
        rb = mgr._rebuilder
        _drive(mgr, 30, 100)  # settle well inside the staleness window
        real_build = rb.build_sync
        fail_once = {"left": 1}

        def flaky(req):
            if fail_once["left"]:
                fail_once["left"] -= 1
                raise RuntimeError("transient solver failure")
            return real_build(req)

        rb.build_sync = flaky
        ex.run_all()  # build 1 fails; error stashed
        with pytest.raises(RuntimeError, match="rebuild failed"):
            _drive(mgr, 30, 1)
        # the estimate has NOT moved materially — recovery must not
        # depend on fresh drift
        _drive(mgr, 30, 5)
        assert rb.builds_started == 2  # re-requested after the failure
        ex.run_all()
        _drive(mgr, 30, 2)
        assert mgr.surface_swaps == 1  # the retry adopted
        est = mgr.estimators["esp_now"]
        assert mgr.surface.in_envelope("esp_now", est.packet_time_estimate,
                                       est.loss_estimate)

    def test_async_requires_surface_capable_solver(self):
        with pytest.raises(ValueError, match="async_rebuild"):
            AdaptiveSplitManager(
                cost_model=paper_cost_model("mobilenet_v2", "esp_now"),
                protocols=dict(PROTOCOLS), n_devices=2,
                solver="first_fit", async_rebuild=True)


class TestFleetSharedRebuilder:
    def test_fleet_drift_batches_into_one_multi_size_solve(self):
        """Two managers drift while sharing one rebuilder: ONE
        build_surfaces call answers both fleet sizes, and each manager
        adopts its own node-identical surface."""
        ex = ManualExecutor()
        mgrs = fleet_managers(
            paper_cost_model("mobilenet_v2", "esp_now"), dict(PROTOCOLS),
            (2, 3), solver="optimal_dp", surface_grid=GRID,
            async_rebuild=ex)
        rb = mgrs[2]._rebuilder
        assert rb is mgrs[3]._rebuilder  # ONE shared rebuilder
        # both managers drift before any build launches: both sizes queue
        lat = 30 * ESP_NOW.transmission_latency_s(NBYTES)
        mgrs[2].observe("esp_now", NBYTES, lat * 167)  # jump past envelope
        mgrs[3].observe("esp_now", NBYTES, lat * 167)
        assert sorted(rb._queued) == [2, 3]
        # next polls launch ONE build carrying BOTH sizes
        _drive(mgrs[2], 5000, 30)
        _drive(mgrs[3], 5000, 30)
        assert rb.builds_started == 1
        assert rb.last_request.sizes == (2, 3)
        assert ex.pending() == 1
        req = rb.last_request
        ex.run_all()
        _drive(mgrs[2], 5000, 1)
        _drive(mgrs[3], 5000, 1)
        assert mgrs[2].surface_swaps == 1 and mgrs[3].surface_swaps == 1
        sync = rb.build_sync(req)
        _assert_node_identical(mgrs[2].surface, sync[2])
        _assert_node_identical(mgrs[3].surface, sync[3])
        assert mgrs[2].surface.n_devices == 2
        assert mgrs[3].surface.n_devices == 3

    def test_fleet_async_accepts_prebuilt_rebuilder(self):
        m = paper_cost_model("mobilenet_v2", "esp_now")
        rb = SurfaceRebuilder(m, dict(PROTOCOLS), solver="batched_dp",
                              executor=ManualExecutor(), **GRID)
        mgrs = fleet_managers(m, dict(PROTOCOLS), (2, 3),
                              solver="optimal_dp", surface_grid=GRID,
                              async_rebuild=rb)
        assert mgrs[2]._rebuilder is rb and mgrs[3]._rebuilder is rb


class TestSurfaceCovers:
    def test_covers_matches_in_envelope(self):
        mgr = _mgr(ManualExecutor())
        surf = mgr.surface
        pt = ESP_NOW.packet_time_s()
        good = {name: (p.packet_time_s(), p.loss_p)
                for name, p in PROTOCOLS.items()}
        assert surf.covers(good)
        bad = dict(good, esp_now=(pt * 1e4, 0.0))
        assert not surf.covers(bad)

    def test_stale_window_resets_on_return_to_envelope(self):
        """After re-entering the envelope, the next excursion must
        re-solve immediately (fresh staleness window), not serve the
        previous excursion's stale state."""
        ex = ManualExecutor()
        mgr = _mgr(ex)
        _drive(mgr, 5000, 80)
        assert mgr._fallback_state is not None
        _drive(mgr, 1, 200)  # recover into the envelope
        assert mgr._fallback_state is None
        f0 = mgr.exact_fallbacks
        _drive(mgr, 5000, 5)
        assert mgr.exact_fallbacks > f0  # fresh excursion re-solved


class TestDefaultExecutor:
    def test_background_thread_rebuild_adopts(self):
        """async_rebuild=True uses a real worker thread; the build is
        awaited explicitly (executor shutdown barrier), never slept on."""
        mgr = _mgr(True)
        _drive(mgr, 30, 100)
        rb = mgr.rebuilder
        assert rb is mgr._rebuilder and rb.builds_started >= 1
        rb.shutdown()  # barrier: waits for the in-flight build
        _drive(mgr, 30, 2)
        assert mgr.surface_swaps >= 1
        est = mgr.estimators["esp_now"]
        assert mgr.surface.in_envelope("esp_now", est.packet_time_estimate,
                                       est.loss_estimate)
        mgr.close()  # idempotent with the earlier shutdown

    def test_shutdown_is_terminal(self):
        """Regression: after shutdown() a queued request must NOT
        resurrect a fresh thread pool on the next poll."""
        ex = ManualExecutor()
        mgr = _mgr(ex)
        rb = mgr._rebuilder
        rb.shutdown()
        _drive(mgr, 5000, 20)  # drift: requests queue...
        assert rb._queued  # ...but nothing ever launches
        assert rb.builds_started == 0
        assert ex.pending() == 0
        assert rb._executor is ex  # and no internal pool was created
        # observes still flow (stale serves + bounded fallbacks)
        assert mgr._step == 20

    def test_close_leaves_shared_rebuilder_running(self):
        m = paper_cost_model("mobilenet_v2", "esp_now")
        rb = SurfaceRebuilder(m, dict(PROTOCOLS), solver="batched_dp",
                              executor=ManualExecutor(), **GRID)
        mgrs = fleet_managers(m, dict(PROTOCOLS), (2,),
                              solver="optimal_dp", surface_grid=GRID,
                              async_rebuild=rb)
        mgrs[2].close()
        assert not rb._closed  # shared: the owner shuts it down
        rb.shutdown()
        assert rb._closed


class TestLossClampCeiling:
    def test_loss_above_clamp_refits_identically(self):
        """refit_link maps every loss at or above LOSS_CLAMP to the
        identical link — the precondition for clamping lookups."""
        from repro.core.surface import LOSS_CLAMP, refit_link

        pt = ESP_NOW.packet_time_s() * 10
        assert refit_link(ESP_NOW, pt, 0.97) \
            == refit_link(ESP_NOW, pt, LOSS_CLAMP)

    def test_loss_above_clamp_stays_in_envelope(self):
        """Regression: a loss estimate above 0.9 could never land inside
        any envelope (axes cap at the clamp), so every rebuild cycle
        missed and re-queued forever. Lookups now clamp the loss
        coordinate exactly."""
        from repro.core.surface import build_surface

        m = paper_cost_model("mobilenet_v2", "esp_now")
        surf = build_surface(m, dict(PROTOCOLS), 2,
                             pt_scale=(1.0, 4.0), loss_p=(0.0, 0.9))
        assert surf.in_envelope("esp_now", ESP_NOW.packet_time_s(), 0.97)
        hit = surf.lookup("esp_now", ESP_NOW.packet_time_s(), 0.97)
        ref = surf.lookup("esp_now", ESP_NOW.packet_time_s(), 0.9)
        assert hit.in_envelope
        assert hit.splits == ref.splits
        assert hit.latency_s == ref.latency_s
        # but an axis BELOW the clamp still rejects heavier loss
        small = build_surface(m, dict(PROTOCOLS), 2,
                              pt_scale=(1.0, 4.0), loss_p=(0.0, 0.3))
        assert not small.in_envelope("esp_now", ESP_NOW.packet_time_s(), 0.5)

    def test_saturated_loss_rebuild_converges(self):
        """End to end: estimator loss forced past the clamp, drift
        triggers ONE re-centered rebuild whose adopted surface covers
        the saturated state — no endless rebuild cycle."""
        ex = ManualExecutor()
        mgr = _mgr(ex)
        est = mgr.estimators["esp_now"]
        est._loss = 0.95  # beyond the clamp; raw EWMA can reach this
        _drive(mgr, 30, 100)
        for _ in range(4):  # cycles enough for any re-centering
            ex.run_all()
            _drive(mgr, 30, 2)
        assert mgr.surface_swaps >= 1
        assert mgr.surface.in_envelope("esp_now", est.packet_time_estimate,
                                       est.loss_estimate)
        b0 = mgr._rebuilder.builds_started
        _drive(mgr, 30, 40)
        assert mgr._rebuilder.builds_started == b0  # no rebuild churn


class TestObserveStateSingleSourcing:
    def test_envelope_lookup_uses_estimate_accessors(self, monkeypatch):
        """Regression (warm-up window): observe() must read the estimator
        through packet_time_estimate/loss_estimate — the same accessors
        the re-solve path prices with — not the raw EWMA fields. With
        the accessors reporting an out-of-envelope state, a healthy raw
        field must NOT keep the lookup on the surface."""
        from repro.core.adaptive import LinkEstimator

        mgr = AdaptiveSplitManager(
            cost_model=paper_cost_model("mobilenet_v2", "esp_now"),
            protocols=dict(PROTOCOLS), n_devices=2,
            solver="optimal_dp", surface_grid=GRID)
        far = ESP_NOW.packet_time_s() * 1e6
        monkeypatch.setattr(LinkEstimator, "packet_time_estimate",
                            property(lambda self: far))
        _drive(mgr, 1, 1)  # raw fields stay healthy/in-envelope
        assert mgr.surface_hits == 0
        assert mgr.exact_fallbacks == 1  # the accessor view won

    def test_warmup_loss_view_is_consistent_across_paths(self):
        """During the loss warm-up window the surface lookup and the
        exact re-solve must see the SAME loss value."""
        lossy = {name: replace(p, loss_p=0.10)
                 for name, p in PROTOCOLS.items()}
        mgr = AdaptiveSplitManager(
            cost_model=paper_cost_model("mobilenet_v2", "esp_now"),
            protocols=lossy, n_devices=2, solver="optimal_dp",
            surface_grid={"pt_scale": (1.0, 4.0, 16.0),
                          "loss_p": (None, 0.0, 0.3)})
        est = mgr.estimators["esp_now"]
        # one lucky retry-free hop inside the warm-up window
        mgr.observe("esp_now", NBYTES,
                    ESP_NOW.transmission_latency_s(NBYTES))
        assert est.n_obs <= est.loss_warmup  # still warming up
        assert mgr.surface_hits == 1  # primed loss stayed in-envelope
        # the state the lookup used IS the state the re-solve prices
        assert est.current_profile().loss_p == pytest.approx(
            est.loss_estimate)


class TestPollVersioned:
    def _rebuilt(self, ex):
        """A rebuilder with one completed build for size 2."""
        rb = SurfaceRebuilder(paper_cost_model("mobilenet_v2", "esp_now"),
                              dict(PROTOCOLS), executor=ex, **GRID)
        drift = {"esp_now": (20 * ESP_NOW.packet_time_s(), 0.0)}
        rb.request(2, drift)
        assert rb.poll_versioned(2) is None  # launches
        ex.run_all()
        return rb

    def test_handover_carries_generation_exactly_once(self):
        ex = ManualExecutor()
        rb = self._rebuilt(ex)
        got = rb.poll_versioned(2)
        assert got is not None
        gen, surf = got
        assert gen == 1
        assert isinstance(surf, DegradationSurface)
        assert rb.poll_versioned(2) is None  # exactly once
        assert rb.poll(2) is None

    def test_legacy_poll_unwraps_the_same_handover(self):
        ex = ManualExecutor()
        rb = self._rebuilt(ex)
        surf = rb.poll(2)
        assert isinstance(surf, DegradationSurface)
        assert rb.poll_versioned(2) is None


class TestRebuildFanout:
    def _fanout_with_build(self):
        from repro.core.async_replan import RebuildFanout

        ex = ManualExecutor()
        rb = SurfaceRebuilder(paper_cost_model("mobilenet_v2", "esp_now"),
                              dict(PROTOCOLS), executor=ex, **GRID)
        fo = RebuildFanout(rb)
        drift = {"esp_now": (20 * ESP_NOW.packet_time_s(), 0.0)}
        rb.request(2, drift)
        assert fo.refresh(2) is False  # launches; nothing completed yet
        ex.run_all()
        return fo, ex, drift

    def test_one_build_redistributes_to_every_handle(self):
        fo, ex, _ = self._fanout_with_build()
        handles = [fo.view() for _ in range(5)]
        surfs = [h.poll(2) for h in handles]
        assert all(s is not None for s in surfs)
        assert len({id(s) for s in surfs}) == 1  # the SAME surface object
        assert [h.adoptions for h in handles] == [[(2, 1)]] * 5
        # steady state after adoption: every handle answers None
        assert all(h.poll(2) is None for h in handles)

    def test_refresh_publishes_then_is_idempotent(self):
        fo, ex, _ = self._fanout_with_build()
        assert fo.refresh(2) is True
        assert fo.latest(2)[0] == 1
        assert fo.refresh(2) is False  # drained: exactly-once upstream
        assert fo.seq == 1

    def test_refresh_rejects_older_generation(self):
        fo, ex, drift = self._fanout_with_build()
        assert fo.refresh(2) is True
        newer = fo.latest(2)
        # force an out-of-order completion into the upstream handover
        fo.rebuilder._results[2] = (0, newer[1])
        fo.rebuilder._maybe_actionable = True
        assert fo.refresh(2) is False  # gen 0 <= adopted gen 1 upstream
        assert fo.latest(2) == newer

    def test_handle_never_readopts_older_generation(self):
        fo, ex, _ = self._fanout_with_build()
        h = fo.view()
        assert h.poll(2) is not None  # adopted gen 1
        stale = fo.latest(2)[1]
        fo._latest[2] = (0, stale)  # regress the shared map by force
        fo.seq += 1
        assert h.poll(2) is None  # refused: gen 0 <= adopted gen 1
        assert h.adoptions == [(2, 1)]
        # a FRESH handle does adopt from the (regressed) map — per-handle
        # monotonicity, not global erasure
        assert fo.view().poll(2) is stale

    def test_handle_request_reaches_shared_rebuilder(self):
        fo, ex, drift = self._fanout_with_build()
        h = fo.view()
        assert h.request(3, drift) == "queued"
        assert h.poll(3) is None  # launches the size-3 build
        assert fo.rebuilder.builds_started == 2
        ex.run_all()
        assert h.poll(3) is not None
        assert h.shutdown() is None  # no-op: shared rebuilder stays up
        assert fo.rebuilder._closed is False


class TestBoundedQueuedStates:
    def test_overflow_folds_into_last_entry_by_max(self):
        rb = SurfaceRebuilder(paper_cost_model("mobilenet_v2", "esp_now"),
                              dict(PROTOCOLS), executor=ManualExecutor(),
                              max_queued_states=2, **GRID)
        pt = ESP_NOW.packet_time_s()
        rb.request(2, {"esp_now": (10 * pt, 0.01)})
        rb.request(2, {"esp_now": (20 * pt, 0.02)})
        # past the cap: folded into the LAST entry, per-protocol max
        assert rb.request(2, {"esp_now": (15 * pt, 0.05)}) == "coalesced"
        assert rb.request(2, {"esp_now": (40 * pt, 0.03)}) == "coalesced"
        assert len(rb._queued[2]) == 2
        assert rb._queued[2][0] == {"esp_now": (10 * pt, 0.01)}
        folded = rb._queued[2][1]["esp_now"]
        assert folded == (40 * pt, 0.05)  # max over the folded requests

    def test_distinct_requesters_all_recenter_the_build(self):
        """Regression: a single merged dict kept only the LAST
        requester's target — sessions drifting to different points got a
        surface centered on one of them. Every under-cap requester's
        state must reach recentered_axes."""
        ex = ManualExecutor()
        rb = SurfaceRebuilder(paper_cost_model("mobilenet_v2", "esp_now"),
                              dict(PROTOCOLS), executor=ex, **GRID)
        pt = ESP_NOW.packet_time_s()
        rb.request(2, {"esp_now": (10 * pt, 0.0)})
        rb.request(2, {"esp_now": (30 * pt, 0.0)})
        rb.poll(2)  # launch
        req = rb.last_request
        # both requesters' ratios survive (x the 1.0 pad factor)
        assert any(abs(s - 10.0) < 1e-9 for s in req.pt_scale)
        assert any(abs(s - 30.0) < 1e-9 for s in req.pt_scale)


class TestExecutorContract:
    """The executor protocol: submit() is required, shutdown() is
    optional, and a dead executor is a failed build — never a crash in
    the serving thread or in close()."""

    def _rb(self, executor):
        return SurfaceRebuilder(paper_cost_model("mobilenet_v2", "esp_now"),
                                dict(PROTOCOLS), executor=executor, **GRID)

    def test_dead_process_pool_surfaces_error_not_crash(self):
        """Regression: submitting to an already-terminated
        ProcessPoolExecutor raised out of poll() and left _inflight
        wedged. The submit failure must surface like any failed build
        (stashed, re-raised once) and leave the rebuilder serviceable."""
        pool = cpu_process_pool()
        pool.shutdown(wait=True)  # dead before the rebuilder ever submits
        rb = self._rb(pool)
        pt = ESP_NOW.packet_time_s()
        assert rb.request(2, {"esp_now": (10 * pt, 0.0)}) == "queued"
        with pytest.raises(RuntimeError,
                           match="async surface rebuild failed"):
            rb.poll(2)  # launches: submit raises, error is stashed
            rb.poll(2)  # stashed error re-raised here at the latest
        assert rb.inflight() is None  # not wedged on the failed launch
        # still serviceable: errors re-raise once, then polls are clean
        assert rb.poll(2) is None
        # and shutdown() tolerates the dead injected pool (and is
        # idempotent)
        rb.shutdown()
        rb.shutdown()

    def test_shutdown_tolerates_executor_without_shutdown(self):
        """ManualExecutor has no shutdown() — the contract says that is
        fine, including after the rebuilder created nothing itself."""
        rb = self._rb(ManualExecutor())
        pt = ESP_NOW.packet_time_s()
        rb.request(2, {"esp_now": (10 * pt, 0.0)})
        rb.shutdown()
        rb.shutdown()

    def test_shutdown_tolerates_broken_own_executor(self):
        """Even the internally created executor is closed defensively:
        a shutdown() that raises must not escape close()."""
        class _ExplodingExecutor:
            def submit(self, fn):  # pragma: no cover - never launched
                raise AssertionError("not used")

            def shutdown(self, wait=True):
                raise OSError("pool already reaped")

        rb = self._rb(None)
        rb._executor = _ExplodingExecutor()
        rb._own_executor = True
        rb.shutdown()  # must swallow the OSError
        assert rb._executor is None

    def test_process_pool_build_adopts_and_matches_sync(self):
        """Live process pool: the pickled-spec build path publishes with
        the same generation/swap semantics and the adopted surface is
        node-identical to the synchronous build."""
        import time as _time

        pool = cpu_process_pool()
        rb = self._rb(pool)
        try:
            pt = ESP_NOW.packet_time_s()
            rb.request(2, {"esp_now": (10 * pt, 0.01)})
            got = None
            deadline = _time.monotonic() + 120.0
            while got is None and _time.monotonic() < deadline:
                got = rb.poll(2)
                if got is None:
                    _time.sleep(0.05)
            assert got is not None, "process-pool rebuild never adopted"
            assert rb.builds_completed == 1 and rb.inflight() is None
            _assert_node_identical(got, rb.build_sync(rb.last_request)[2])
        finally:
            rb.shutdown()
            pool.shutdown(wait=True)

    def test_process_pool_refuses_device_backend(self):
        """A chip belongs to one process: a pool rebuilder must not ship
        device-backend builds to workers that would contend for it."""
        pool = cpu_process_pool()
        try:
            with pytest.raises(ValueError, match="backend='numpy' only"):
                SurfaceRebuilder(paper_cost_model("mobilenet_v2", "esp_now"),
                                 dict(PROTOCOLS), solver="batched_dp",
                                 backend="pallas", executor=pool, **GRID)
        finally:
            pool.shutdown(wait=True)

    def test_cpu_pool_workers_pin_jax_to_cpu(self, monkeypatch):
        """The pool's initializer pins each worker's JAX to the CPU even
        when the parent's environment names no platform."""
        import os

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        pool = cpu_process_pool()
        try:
            assert pool.submit(os.getenv, "JAX_PLATFORMS").result(
                timeout=120) == "cpu"
        finally:
            pool.shutdown(wait=True)
