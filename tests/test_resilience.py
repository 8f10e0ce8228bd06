"""Fault-tolerant serving: isolation, deadlines, backpressure, watchdog.

Covers the ISSUE 8 resilience layer end to end with the deterministic
fault injector (``repro.testing.faults``):

* request isolation — a tagged executor fault fails exactly the
  offending request (typed ``RequestFailedError``) while its step-mates
  complete with reference-exact results; untagged faults degrade the
  step to per-image dispatch instead of failing the batch.
* deadlines — expiry at admission (never served) vs mid-flight
  (computed, still failed: the contract is the deadline).
* backpressure — the bounded queue under all three policies, including
  the oversized-request pre-reject that keeps ``block`` deadlock-free.
* watchdog — a stalled staging worker fails over to synchronous prepass
  with correct results.
* exactly-once — every submitted request resolves exactly once under a
  seeded fault storm; ``DrainTimeout`` instead of silent drops when a
  drain budget is exhausted.

Every injected fault is a pure function of ``(seed, kind, index)`` —
reruns reproduce bit-identical failure patterns.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.deform import DeformableConvParams, randomize_offset_conv
from repro.models import lm
from repro.models.dcn_models import DcnNetConfig, init_dcn_net
from repro.models.params import Maker
from repro.runtime import GraphConfig
from repro.serving import (DcnServingEngine, DeadlineExceededError,
                           DecodeEngine, DrainTimeout, QueueFullError,
                           Request, RequestFailedError)
from repro.testing import ALL_FAULT_KINDS, FaultError, FaultInjector, FaultPlan


def _dcn_case(seed=2):
    cfg = DcnNetConfig(name="vgg19", n_deform=2, img_size=16,
                       width_mult=0.125, num_classes=4)
    key = jax.random.PRNGKey(seed)
    params = init_dcn_net(key, cfg)
    params["convs"] = [
        randomize_offset_conv(p, jax.random.fold_in(key, 100 + i),
                              2.0 / p.w.shape[2])
        if isinstance(p, DeformableConvParams) else p
        for i, p in enumerate(params["convs"])]
    return cfg, params


@pytest.fixture(scope="module")
def dcn_setup():
    return _dcn_case()


def _engine(dcn_setup, **kw):
    cfg, params = dcn_setup
    kw.setdefault("graph", GraphConfig(tile=4))
    return DcnServingEngine(params, cfg, **kw)


def _images(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, 16, 16, 3)).astype(np.float32)


def _reference(dcn_setup, xs):
    cfg, params = dcn_setup
    ref = DcnServingEngine(params, cfg, graph=GraphConfig(tile=4))
    return np.asarray(ref.infer(jnp.asarray(xs)))


class TestRequestIsolation:
    def test_tagged_dispatch_fault_isolates_one_request(self, dcn_setup):
        """A dispatch fault naming its image fails exactly that request;
        the evict-and-retry step serves the step-mates with results
        equal to a fault-free engine."""
        inj = FaultInjector(kinds=("dispatch",), rate=1.0, max_fires=1,
                            seed=3)
        eng = _engine(dcn_setup, slots=4, faults=inj)
        xs = _images(3, seed=1)
        reqs = [eng.submit(xs[i]) for i in range(3)]
        done = eng.drain()
        assert sorted(r.rid for r in done) == [r.rid for r in reqs]
        assert inj.fired["dispatch"] == 1
        failed = [r for r in reqs if r.failed]
        assert len(failed) == 1
        with pytest.raises(RequestFailedError) as ei:
            failed[0].result()
        assert isinstance(ei.value.__cause__, FaultError)
        ref = _reference(dcn_setup, xs)
        for i, r in enumerate(reqs):
            if not r.failed:
                np.testing.assert_allclose(r.result()[0], ref[i],
                                           rtol=2e-4, atol=2e-4)
        s = eng.stats
        assert s["step_retries"] == 1
        assert s["degraded_steps"] == 0
        assert s["requests_failed"] == 1

    def test_tagged_prepass_fault_isolates_one_request(self, dcn_setup):
        inj = FaultInjector(kinds=("prepass",), rate=1.0, max_fires=1,
                            seed=5)
        eng = _engine(dcn_setup, slots=4, faults=inj)
        xs = _images(3, seed=2)
        reqs = [eng.submit(xs[i]) for i in range(3)]
        eng.drain()
        assert sum(r.failed for r in reqs) == 1
        assert eng.stats["step_retries"] == 1
        ref = _reference(dcn_setup, xs)
        for i, r in enumerate(reqs):
            if not r.failed:
                np.testing.assert_allclose(r.result()[0], ref[i],
                                           rtol=2e-4, atol=2e-4)

    def test_untagged_transient_fault_degrades_step(self, dcn_setup):
        """A fault that cannot name its image degrades the step to
        per-image batched dispatch — every request still completes
        correctly (the fault was transient)."""
        inj = FaultInjector(kinds=("dispatch",), rate=1.0, max_fires=1,
                            tag_image=False, seed=7)
        eng = _engine(dcn_setup, slots=4, faults=inj)
        xs = _images(3, seed=3)
        reqs = [eng.submit(xs[i]) for i in range(3)]
        eng.drain()
        assert all(r.done and not r.failed for r in reqs)
        s = eng.stats
        assert s["degraded_steps"] == 1
        assert s["requests_failed"] == 0
        ref = _reference(dcn_setup, xs)
        got = np.concatenate([r.result() for r in reqs])
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)

    def test_persistent_untagged_fault_fails_all_typed(self, dcn_setup):
        """Every dispatch faulting (untagged, unlimited): the degraded
        per-image runs capture each image's exception — all requests
        resolve with typed errors, nothing deadlocks or goes missing."""
        inj = FaultInjector(kinds=("dispatch",), rate=1.0,
                            tag_image=False, seed=9)
        eng = _engine(dcn_setup, slots=4, faults=inj)
        reqs = [eng.submit(_images(1, seed=20 + i)) for i in range(3)]
        done = eng.drain(max_steps=50)
        assert sorted(r.rid for r in done) == [r.rid for r in reqs]
        for r in reqs:
            assert r.failed and isinstance(r.error, RequestFailedError)
            assert isinstance(r.error.__cause__, FaultError)
        s = eng.stats
        assert s["requests_failed"] == 3
        assert s["degraded_steps"] >= 1

    def test_degraded_step_runs_batch_fused_programs(self, dcn_setup):
        """A step whose dispatch faults and whose retry faults too
        degrades to its images one at a time through the compiled
        batch-fused programs: the survivors equal a clean step's output,
        and every group of every survivor ran its execute program."""
        inj = FaultInjector(kinds=("dispatch",), rate=1.0, max_fires=2,
                            seed=3)
        eng = _engine(dcn_setup, slots=4, faults=inj)
        xs = _images(3, seed=6)
        reqs = [eng.submit(xs[i]) for i in range(3)]
        eng.drain()
        programs = eng.exec_programs    # process-wide counter: read now
        assert inj.fired["dispatch"] == 2
        s = eng.stats
        assert s["step_retries"] == 1 and s["degraded_steps"] == 1
        survivors = [i for i, r in enumerate(reqs) if not r.failed]
        assert len(survivors) == 2
        cfg, params = dcn_setup
        clean = DcnServingEngine(params, cfg, graph=GraphConfig(tile=4),
                                 slots=4)
        clean_reqs = [clean.submit(xs[i]) for i in range(3)]
        clean.drain()
        assert clean.stats["steps"] == 1
        for i in survivors:
            np.testing.assert_allclose(reqs[i].result(),
                                       clean_reqs[i].result(),
                                       rtol=2e-4, atol=2e-4)
        # The faulted step and its retry fail before any group executes;
        # one clean step runs each group's program once.
        assert clean.exec_programs > 0
        assert programs == len(survivors) * clean.exec_programs

    def test_cache_miss_storm_correct_but_cold(self, dcn_setup):
        """A cache_miss storm (every key salted) forces rebuilds: image
        hits stay 0 where a replay would normally hit, and results stay
        correct — the cache is an optimization, never a correctness
        dependency."""
        inj = FaultInjector(kinds=("cache_miss",), rate=1.0, seed=11)
        eng = _engine(dcn_setup, slots=1, faults=inj)
        x = _images(1, seed=4)
        r1 = eng.submit(x)
        r2 = eng.submit(x)                   # replay: would hit when healthy
        eng.drain()
        assert inj.fired["cache_miss"] > 0
        assert eng.stats["image_hits"] == 0
        ref = _reference(dcn_setup, x)
        np.testing.assert_allclose(r1.result(), ref, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(r2.result(), ref, rtol=2e-4, atol=2e-4)

    def test_exactly_once_under_fault_storm(self, dcn_setup):
        """Seeded multi-kind storm: every request resolves exactly once
        — failed requests carry typed errors, survivors match the
        fault-free reference."""
        inj = FaultInjector(kinds=("prepass", "dispatch"), rate=0.3,
                            seed=13)
        eng = _engine(dcn_setup, slots=4, faults=inj)
        xs = _images(8, seed=5)
        reqs = [eng.submit(xs[i]) for i in range(8)]
        done = eng.drain(max_steps=100)
        rids = [r.rid for r in done]
        assert sorted(rids) == [r.rid for r in reqs]
        assert len(rids) == len(set(rids))
        assert eng.drain() == []             # nothing resolves twice
        assert inj.total_fired > 0           # the storm actually fired
        ref = _reference(dcn_setup, xs)
        for i, r in enumerate(reqs):
            assert r.done
            if r.failed:
                assert isinstance(r.error, RequestFailedError)
            else:
                np.testing.assert_allclose(r.result()[0], ref[i],
                                           rtol=2e-4, atol=2e-4)
        s = eng.stats
        assert s["requests_failed"] == sum(r.failed for r in reqs)

    def test_failure_counters_in_metrics_snapshot(self, dcn_setup):
        """Every failure counter ``stats`` reports appears in
        ``metrics_snapshot()`` under its registry name."""
        inj = FaultInjector(kinds=("dispatch",), rate=1.0, max_fires=1,
                            seed=3)
        eng = _engine(dcn_setup, slots=2, faults=inj)
        for i in range(2):
            eng.submit(_images(1, seed=30 + i))
        eng.drain()
        snap = eng.metrics_snapshot()
        assert snap["serving.requests_failed"] == 1
        for name in ("serving.deadline_expired", "serving.queue_rejected",
                     "serving.queue_shed", "serving.step_retries",
                     "serving.degraded_steps",
                     "serving.watchdog_failovers"):
            assert name in snap


class TestDeadlines:
    def test_expiry_at_admission_never_served(self, dcn_setup):
        """A request whose deadline passes while queued fails at
        admission without ever occupying a slot or burning compute."""
        now = [0.0]
        eng = _engine(dcn_setup, slots=1, clock=lambda: now[0])
        r1 = eng.submit(_images(1, seed=40))
        r2 = eng.submit(_images(1, seed=41), deadline_s=0.5)
        now[0] = 1.0
        first = eng.step()                   # serves r1
        assert [r.rid for r in first] == [r1.rid]
        second = eng.step()                  # r2 expires at admission
        assert [r.rid for r in second] == [r2.rid]
        assert r2.failed and isinstance(r2.error, DeadlineExceededError)
        with pytest.raises(DeadlineExceededError):
            r2.result()
        s = eng.stats
        assert s["deadline_expired"] == 1 and s["requests_failed"] == 1
        assert s["images"] == 1              # r2 was never executed
        assert s["steps"] == 1               # the expiry step ran no grid
        assert s["latency"]["count"] == 1    # failures never enter latency

    def test_expiry_mid_flight_after_compute(self, dcn_setup):
        """Admitted in time, completed past the deadline: the image was
        computed but the request still fails — the contract is the
        deadline, not the compute."""
        ticks = [0.0, 0.0, 1.0]              # submit, admission, completion
        clock = lambda: ticks.pop(0) if ticks else 1.0  # noqa: E731
        eng = _engine(dcn_setup, slots=1, clock=clock)
        r = eng.submit(_images(1, seed=42), deadline_s=0.5)
        done = eng.step()
        assert [q.rid for q in done] == [r.rid]
        assert r.failed and isinstance(r.error, DeadlineExceededError)
        s = eng.stats
        assert s["deadline_expired"] == 1
        assert s["images"] == 1              # it WAS served, then expired
        assert s["latency"]["count"] == 0

    def test_deadline_validation(self, dcn_setup):
        eng = _engine(dcn_setup)
        with pytest.raises(ValueError, match="deadline_s"):
            eng.submit(_images(1), deadline_s=0.0)
        with pytest.raises(ValueError, match="deadline_s"):
            eng.submit(_images(1), deadline_s=-1.0)


class TestBackpressure:
    def test_reject_policy_raises_queue_full(self, dcn_setup):
        eng = _engine(dcn_setup, slots=1, max_queue=2,
                      queue_policy="reject")
        r1 = eng.submit(_images(1, seed=50))
        r2 = eng.submit(_images(1, seed=51))
        with pytest.raises(QueueFullError):
            eng.submit(_images(1, seed=52))
        assert eng.stats["queue_rejected"] == 1
        done = eng.drain()
        assert {r.rid for r in done} == {r1.rid, r2.rid}
        assert all(not r.failed for r in (r1, r2))

    def test_shed_oldest_resolves_victim_on_handle(self, dcn_setup):
        """Policy shed-oldest evicts the oldest queued request; its
        handle resolves immediately with a RequestFailedError caused by
        QueueFullError, and it never appears in step/drain returns."""
        eng = _engine(dcn_setup, slots=1, max_queue=2,
                      queue_policy="shed-oldest")
        r1 = eng.submit(_images(1, seed=53))
        r2 = eng.submit(_images(1, seed=54))
        r3 = eng.submit(_images(1, seed=55))  # sheds r1
        assert r1.done and r1.failed
        assert isinstance(r1.error, RequestFailedError)
        assert isinstance(r1.error.__cause__, QueueFullError)
        done = eng.drain()
        assert {r.rid for r in done} == {r2.rid, r3.rid}
        s = eng.stats
        assert s["queue_shed"] == 1 and s["requests_failed"] == 1

    def test_block_policy_waits_for_room(self, dcn_setup):
        """A blocked submitter is released by step()'s admission and the
        late request completes — no deadlock, nothing dropped."""
        eng = _engine(dcn_setup, slots=1, max_queue=1,
                      queue_policy="block")
        r1 = eng.submit(_images(1, seed=56))
        late: list = []

        def client():
            late.append(eng.submit(_images(1, seed=57)))

        t = threading.Thread(target=client)
        t.start()
        done: list = []
        for _ in range(50):
            done.extend(eng.step())
            if not t.is_alive() and len(done) == 2:
                break
        t.join(timeout=10)
        assert not t.is_alive()
        done.extend(eng.drain())
        assert {r.rid for r in done} == {r1.rid, late[0].rid}
        assert all(not r.failed for r in (r1, late[0]))

    def test_oversized_request_always_rejected(self, dcn_setup):
        """Wider than max_queue can never fit — rejected up front even
        under policy block (waiting would deadlock forever)."""
        eng = _engine(dcn_setup, slots=1, max_queue=2,
                      queue_policy="block")
        with pytest.raises(QueueFullError, match="exceeds max_queue"):
            eng.submit(_images(3, seed=58))
        assert eng.stats["queue_rejected"] == 1
        assert eng.queue_depth == 0

    def test_queue_config_validation(self, dcn_setup):
        with pytest.raises(ValueError, match="queue_policy"):
            _engine(dcn_setup, queue_policy="drop-newest")
        with pytest.raises(ValueError, match="max_queue"):
            _engine(dcn_setup, max_queue=0)


class TestWatchdog:
    def test_stalled_worker_fails_over_with_correct_results(self,
                                                            dcn_setup):
        """A staging worker stalled past watchdog_s is abandoned; the
        run fails over to synchronous prepass and still produces
        reference-exact results."""
        inj = FaultInjector(kinds=("worker_stall",), rate=1.0,
                            max_fires=1, stall_s=0.4, seed=15)
        eng = _engine(dcn_setup, slots=4,
                      graph=GraphConfig(tile=4, watchdog_s=0.05),
                      faults=inj)
        xs = _images(3, seed=6)
        reqs = [eng.submit(xs[i]) for i in range(3)]
        eng.drain()
        assert inj.fired["worker_stall"] == 1
        assert all(r.done and not r.failed for r in reqs)
        assert eng.stats["watchdog_failovers"] >= 1
        ref = _reference(dcn_setup, xs)
        got = np.concatenate([r.result() for r in reqs])
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)

    def test_watchdog_config_validation(self):
        with pytest.raises(ValueError, match="watchdog_s"):
            GraphConfig(watchdog_s=0.0)
        with pytest.raises(ValueError, match="watchdog_s"):
            GraphConfig(watchdog_s=-1.0)


class TestDrainTimeout:
    def test_dcn_drain_raises_with_stuck_rids(self, dcn_setup):
        eng = _engine(dcn_setup, slots=1)
        reqs = [eng.submit(_images(1, seed=60 + i)) for i in range(3)]
        with pytest.raises(DrainTimeout) as ei:
            eng.drain(max_steps=1)
        assert sorted(ei.value.pending_rids) == [reqs[1].rid, reqs[2].rid]
        assert [r.rid for r in ei.value.finished] == [reqs[0].rid]
        # the stuck work is still there, not dropped: a real drain finishes
        done = eng.drain()
        assert {r.rid for r in done} == {reqs[1].rid, reqs[2].rid}


class TestInputValidation:
    def test_nan_rejected_before_cache(self, dcn_setup):
        """A NaN image is rejected at submit() before its garbage coords
        digest can poison the schedule cache — later clean requests are
        unaffected."""
        eng = _engine(dcn_setup, slots=1)
        bad = _images(1, seed=70)
        bad[0, 3, 3, 1] = np.nan
        with pytest.raises(ValueError, match="finite"):
            eng.submit(bad)
        assert eng.cache.info()["size"] == 0
        assert eng.queue_depth == 0
        x = _images(1, seed=71)
        r = eng.submit(x)
        eng.drain()
        np.testing.assert_allclose(r.result(), _reference(dcn_setup, x),
                                   rtol=2e-4, atol=2e-4)

    def test_inf_rejected(self, dcn_setup):
        eng = _engine(dcn_setup)
        bad = _images(1, seed=72)
        bad[0, 0, 0, 0] = np.inf
        with pytest.raises(ValueError, match="finite"):
            eng.submit(bad)

    def test_corrupted_injector_image_caught_at_submit(self, dcn_setup):
        """The nan_image fault corrupts pre-submit; the engine's front
        door is the isolation under test."""
        inj = FaultInjector(kinds=("nan_image",), rate=1.0, seed=17)
        eng = _engine(dcn_setup)
        x = inj.corrupt(_images(1, seed=73))
        assert inj.fired["nan_image"] == 1
        with pytest.raises(ValueError, match="finite"):
            eng.submit(x)


class TestDecodeEngineResilience:
    @pytest.fixture(scope="class")
    def lm_setup(self):
        cfg = configs.get_config("smollm-360m", smoke=True)
        params = lm.init_lm(Maker("init", jax.random.PRNGKey(40)), cfg)
        return cfg, params

    def test_concurrent_submit_is_thread_safe(self, lm_setup):
        """Regression: the submit queue was a bare list; racing
        submitters could interleave with _admit's pop. Every request
        must decode exactly once."""
        cfg, params = lm_setup
        eng = DecodeEngine(params, cfg, batch=2, max_len=16)
        reqs: list = []
        lock = threading.Lock()

        def client(seed):
            for k in range(2):
                r = Request(seed * 10 + k, [3, 5], max_new=2)
                eng.submit(r)
                with lock:
                    reqs.append(r)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(3)]
        for t in threads:
            t.start()
        while any(t.is_alive() for t in threads):
            eng.step()
        for t in threads:
            t.join()
        done = eng.run()
        assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
        assert len(done) == len(set(r.rid for r in done)) == 6
        assert all(r.done and len(r.out) == 2 for r in reqs)

    def test_run_raises_drain_timeout(self, lm_setup):
        cfg, params = lm_setup
        eng = DecodeEngine(params, cfg, batch=1, max_len=64)
        eng.submit(Request(0, [3, 5], max_new=16))
        eng.submit(Request(1, [3, 5], max_new=16))
        with pytest.raises(DrainTimeout) as ei:
            eng.run(max_steps=2)
        assert set(ei.value.pending_rids) == {0, 1}
        done = eng.run()                     # the work was not dropped
        assert sorted(r.rid for r in done) == [0, 1]


class TestFaultInjector:
    def test_deterministic_across_instances(self):
        pat = []
        for _ in range(2):
            inj = FaultInjector(kinds=("dispatch",), rate=0.4, seed=21)
            fires = []
            for _ in range(30):
                try:
                    inj.check("dispatch", images=4)
                    fires.append(None)
                except FaultError as e:
                    fires.append(e.image)
            pat.append(fires)
        assert pat[0] == pat[1]
        assert any(f is not None for f in pat[0])
        assert any(f is None for f in pat[0])

    def test_rate_zero_never_fires_rate_one_always(self):
        quiet = FaultInjector(kinds=ALL_FAULT_KINDS, rate=0.0, seed=1)
        for _ in range(20):
            quiet.check("dispatch", images=2)
            quiet.check("prepass", image=0)
            assert quiet.miss_salt() is None
        assert quiet.total_fired == 0
        loud = FaultInjector(kinds=("prepass",), rate=1.0, seed=1)
        for i in range(5):
            with pytest.raises(FaultError):
                loud.check("prepass", image=i)
        assert loud.fired["prepass"] == 5

    def test_max_fires_caps_total(self):
        inj = FaultInjector(kinds=("dispatch",), rate=1.0, max_fires=2,
                            seed=2)
        hits = 0
        for _ in range(10):
            try:
                inj.check("dispatch", images=3)
            except FaultError:
                hits += 1
        assert hits == 2 and inj.total_fired == 2

    def test_corrupt_poisons_copy_only(self):
        inj = FaultInjector(kinds=("nan_image",), rate=1.0, seed=4)
        x = np.ones((2, 4, 4, 3), np.float32)
        y = inj.corrupt(x)
        assert y is not x
        assert np.isfinite(x).all()
        assert int(np.isnan(y).sum()) == 1
        off = FaultInjector(kinds=("nan_image",), rate=0.0, seed=4)
        assert off.corrupt(x) is x

    def test_miss_salts_are_unique(self):
        inj = FaultInjector(kinds=("cache_miss",), rate=1.0, seed=6)
        salts = [inj.miss_salt() for _ in range(5)]
        assert all(s is not None for s in salts)
        assert len(set(salts)) == 5

    def test_step_mode_bounds_fires_per_step(self):
        """In step mode an armed kind fires on exactly one consultation
        per step, however many sites consult it."""
        inj = FaultInjector(kinds=("dispatch",), rate=1.0, mode="step",
                            seed=8)
        for _ in range(3):
            inj.begin_step()
            fires = 0
            for _ in range(6):
                try:
                    inj.check("dispatch", images=2)
                except FaultError:
                    fires += 1
            assert fires == 1

    def test_plan_validation(self):
        with pytest.raises(ValueError, match="rate"):
            FaultPlan(rate=1.5)
        with pytest.raises(ValueError, match="mode"):
            FaultPlan(mode="chaos")
        with pytest.raises(ValueError, match="kinds"):
            FaultPlan(kinds=("prepass", "gremlin"))
        with pytest.raises(ValueError, match="not both"):
            FaultInjector(FaultPlan(), rate=0.5)
