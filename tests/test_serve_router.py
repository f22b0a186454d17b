"""Multi-replica router tests.

Three layers, cheapest first. The pure-policy layer (autoscaler
hysteresis, dispatch cost, backpressure accounting) runs on fakes — no
jax, no model. The routing layer drives the real Router over
FakeReplicas that complete requests after a fixed number of steps, so
dispatch/drain/retire behavior is checked without paying for prefill.
The integration layer serves a real smoke model through 1 and 3
in-process replicas and demands greedy token-identity with the
single-engine baseline — placement must be invisible in the output.
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import registry
from repro.models import model as M
from repro.serve import (AutoscaleConfig, Autoscaler, AutoscaleSignal,
                         Completion, EngineConfig, EngineStats,
                         InProcessReplica, ReplicaLoad, Router,
                         RouterConfig, ServeEngine, StatsWindow,
                         dispatch_cost)


# ---------------------------------------------------------------- fakes

class FakeReplica:
    """Completes each request after `latency` step() calls. Mimics the
    engine contract closely enough for dispatch/drain tests: a bounded
    number of concurrent slots, a FIFO queue behind them."""

    def __init__(self, slots=2, latency=2, pages_free=0, pages_per_slot=0):
        self.slots = slots
        self.latency = latency
        self.pages_free = pages_free
        self.pages_per_slot = pages_per_slot
        self.queue = []                 # waiting [uid, tokens]
        self.running = {}               # uid -> steps left
        self.meta = {}                  # uid -> (prompt_len, arrival_s)
        self.done = []
        self.submits = []
        self._stats = EngineStats()
        self.closed = False

    def submit(self, prompt_tokens, max_new, *, temperature=0.0,
               eos_id=None, uid=None, arrival_s=None):
        self.submits.append(uid)
        self.meta[uid] = (len(prompt_tokens), arrival_s or 0.0)
        self.queue.append(uid)
        self._admit()
        return uid

    def _admit(self):
        while self.queue and len(self.running) < self.slots:
            self.running[self.queue.pop(0)] = self.latency

    def step(self):
        if not self.running and not self.queue:
            return False
        for uid in list(self.running):
            self.running[uid] -= 1
            if self.running[uid] <= 0:
                del self.running[uid]
                plen, arr = self.meta[uid]
                self.done.append(Completion(
                    uid=uid, prompt_len=plen, tokens=[1, 2],
                    finish_reason="length", arrival_s=arr))
        self._admit()
        self._stats.decode_steps += 1
        self._stats.decode_tokens += len(self.running)
        return True

    def poll(self):
        out, self.done = self.done, []
        return out

    def load(self):
        return ReplicaLoad(
            queue_depth=len(self.queue),
            free_slots=self.slots - len(self.running), slots=self.slots,
            pages_free=self.pages_free, pages_per_slot=self.pages_per_slot,
            pending=self.pending)

    def stats(self):
        return dataclasses.replace(self._stats)

    @property
    def pending(self):
        return bool(self.queue) or bool(self.running)

    def close(self):
        self.closed = True


# ------------------------------------------------------ policy units

class TestAutoscalerHysteresis:
    def test_up_requires_saturation_and_queued_work(self):
        a = Autoscaler(AutoscaleConfig(max_replicas=4, cooldown=0))
        hot = AutoscaleSignal(decode_util=0.9, queued=3, live=1)
        assert a.observe(hot) == "up"
        # saturated but nothing waiting: adding a replica helps no one
        assert a.observe(dataclasses.replace(hot, queued=0)) is None
        # work waiting but the fleet is idle: dispatch, don't scale
        assert a.observe(dataclasses.replace(hot, decode_util=0.1)) is None

    def test_down_requires_idle_and_empty_queue(self):
        a = Autoscaler(AutoscaleConfig(min_replicas=1, max_replicas=4,
                                       cooldown=0))
        idle = AutoscaleSignal(decode_util=0.05, queued=0, live=3)
        assert a.observe(idle) == "down"
        assert a.observe(dataclasses.replace(idle, queued=1)) is None
        assert a.observe(dataclasses.replace(idle, decode_util=0.5)) is None

    def test_dead_band_between_thresholds(self):
        a = Autoscaler(AutoscaleConfig(up_util=0.75, down_util=0.25,
                                       cooldown=0))
        mid = AutoscaleSignal(decode_util=0.5, queued=2, live=2)
        for _ in range(5):
            assert a.observe(mid) is None

    def test_cooldown_suppresses_consecutive_actions(self):
        a = Autoscaler(AutoscaleConfig(max_replicas=8, cooldown=2))
        hot = AutoscaleSignal(decode_util=1.0, queued=9, live=1)
        assert a.observe(hot) == "up"
        assert a.observe(hot) is None       # cooling
        assert a.observe(hot) is None       # cooling
        assert a.observe(hot) == "up"

    def test_bounds_respected(self):
        a = Autoscaler(AutoscaleConfig(min_replicas=2, max_replicas=3,
                                       cooldown=0))
        hot = AutoscaleSignal(decode_util=1.0, queued=9, live=3)
        assert a.observe(hot) is None       # at max
        idle = AutoscaleSignal(decode_util=0.0, queued=0, live=2)
        assert a.observe(idle) is None      # at min

    def test_config_validation(self):
        with pytest.raises(ValueError, match="min_replicas"):
            AutoscaleConfig(min_replicas=3, max_replicas=2)
        with pytest.raises(ValueError, match="down_util"):
            AutoscaleConfig(up_util=0.2, down_util=0.5)
        with pytest.raises(ValueError, match="window"):
            AutoscaleConfig(window=0)


class TestDispatchCost:
    def test_prefers_headroom_over_depth(self):
        empty = ReplicaLoad(queue_depth=0, free_slots=4, slots=4)
        busy = ReplicaLoad(queue_depth=3, free_slots=0, slots=4)
        assert dispatch_cost(empty) < dispatch_cost(busy)

    def test_pages_bind_headroom(self):
        # 4 free slots but only enough pages for 1 worst-case request
        starved = ReplicaLoad(queue_depth=0, free_slots=4, slots=4,
                              pages_free=5, pages_per_slot=4)
        assert starved.headroom == 1
        roomy = ReplicaLoad(queue_depth=0, free_slots=2, slots=4,
                            pages_free=64, pages_per_slot=4)
        # fewer free slots but pages don't bind: lower cost wins
        assert dispatch_cost(roomy) < dispatch_cost(starved)

    def test_unpaged_ignores_pages(self):
        load = ReplicaLoad(queue_depth=0, free_slots=3, slots=4,
                           pages_free=0, pages_per_slot=0)
        assert load.headroom == 3


class TestStatsWindow:
    def test_delta_subtracts_counters_copies_gauges(self):
        a = EngineStats(decode_steps=10, decode_tokens=40,
                        slots_in_use=3, queue_depth=2, pages_free=7)
        b = EngineStats(decode_steps=16, decode_tokens=64,
                        slots_in_use=1, queue_depth=0, pages_free=9)
        d = b.delta(a)
        assert d.decode_steps == 6 and d.decode_tokens == 24
        # gauges are instantaneous — the window reports b's values
        assert (d.slots_in_use, d.queue_depth, d.pages_free) == (1, 0, 9)

    def test_window_ticks_report_per_interval_rates(self):
        w = StatsWindow()
        first = w.tick(EngineStats(decode_steps=5, decode_tokens=10))
        assert first.decode_steps == 5
        second = w.tick(EngineStats(decode_steps=8, decode_tokens=22))
        assert second.decode_steps == 3 and second.decode_tokens == 12

    def test_decode_utilization(self):
        s = EngineStats(decode_steps=10, decode_tokens=30)
        assert s.decode_utilization(slots=4) == pytest.approx(0.75)
        assert EngineStats().decode_utilization(slots=4) == 0.0


# ----------------------------------------------------- routing on fakes

def fake_router(n=2, **rcfg_kw):
    fake_kw = rcfg_kw.pop("fake_kw", {})
    reps = {}

    def factory(rid):
        reps[rid] = FakeReplica(**fake_kw)
        return reps[rid]

    return Router(factory, RouterConfig(replicas=n, **rcfg_kw)), reps


class TestRouterDispatch:
    def test_spreads_load_across_idle_replicas(self):
        router, reps = fake_router(n=3, fake_kw={"slots": 2})
        for _ in range(6):
            router.submit([1, 2, 3], max_new=4)
        # 6 submits over 3 idle 2-slot replicas: eager dispatch should
        # fill every replica exactly to its slot count
        assert sorted(len(r.submits) for r in reps.values()) == [2, 2, 2]

    def test_ties_break_to_lowest_rid(self):
        router, reps = fake_router(n=3)
        router.submit([1], max_new=2)
        assert reps[0].submits and not reps[1].submits

    def test_skips_replicas_at_queue_cap(self):
        router, reps = fake_router(n=2, replica_queue=1,
                                   fake_kw={"slots": 1, "latency": 99})
        for _ in range(6):
            router.submit([1], max_new=2)
        # each replica: 1 running + 1 queued (the cap); the other 2 wait
        # in the ROUTER queue, not piled onto engine queues
        for r in reps.values():
            assert len(r.queue) <= 1
        assert len(router.queue) == 2

    def test_prefers_replica_with_headroom(self):
        router, reps = fake_router(n=2, fake_kw={"slots": 2, "latency": 99})
        # occupy replica 0 fully out-of-band, then submit via router
        reps[0].submit([1], 2, uid=100)
        reps[0].submit([1], 2, uid=101)
        router.submit([1], max_new=2)
        assert reps[1].submits == [0]

    def test_run_completes_everything_uid_order(self):
        router, _ = fake_router(n=2, fake_kw={"latency": 3})
        uids = [router.submit([1, 2], max_new=4) for _ in range(7)]
        done = router.run()
        assert [c.uid for c in done] == uids
        assert router.stats.completed == 7
        assert not router.pending

    def test_close_closes_replicas(self):
        router, reps = fake_router(n=2)
        router.close()
        assert all(r.closed for r in reps.values())


class TestBackpressure:
    def test_reject_refuses_newcomer_at_limit(self):
        router, _ = fake_router(n=1, queue_limit=2,
                                fake_kw={"slots": 1, "latency": 99})
        got = [router.submit([1], max_new=2) for _ in range(6)]
        # 1 dispatched (fills slot) + 1 engine queue + 2 router queue
        # accepted; the rest refused with None
        accepted = [u for u in got if u is not None]
        assert got[:4] == [0, 1, 2, 3] and got[4:] == [None, None]
        assert router.stats.rejected == 2
        assert router.stats.accepted == len(accepted) == 4
        assert len(router.queue) == 2

    def test_shed_drops_oldest_with_honest_record(self):
        router, _ = fake_router(n=1, queue_limit=2, policy="shed",
                                fake_kw={"slots": 1, "latency": 99})
        for _ in range(6):
            assert router.submit([1, 2, 3], max_new=2) is not None
        assert router.stats.shed == 2
        shed = [c for c in router.completions if c.finish_reason == "shed"]
        # the OLDEST queued requests went overboard, newest kept
        assert [c.uid for c in shed] == [2, 3]
        for c in shed:
            assert c.tokens == [] and c.prompt_len == 3
            assert c.queue_s >= 0.0

    def test_all_requests_accounted_under_exhaustion(self):
        """The honesty invariant: completed + shed + rejected ==
        submitted, under a workload that overflows both slots and the
        router queue."""
        for policy in ("reject", "shed"):
            router, _ = fake_router(n=2, queue_limit=3, policy=policy,
                                    fake_kw={"slots": 1, "latency": 2})
            for _ in range(12):
                router.submit([1], max_new=2)
            router.run()
            st = router.stats
            assert st.completed + st.shed + st.rejected == st.submitted == 12
            assert st.completed == st.dispatched
            if policy == "reject":
                assert st.shed == 0
            else:
                assert st.rejected == 0

    def test_ample_queue_completes_all(self):
        router, _ = fake_router(n=2, queue_limit=64,
                                fake_kw={"slots": 1, "latency": 2})
        for _ in range(12):
            router.submit([1], max_new=2)
        done = router.run()
        assert len(done) == 12
        assert all(c.finish_reason == "length" for c in done)
        assert router.stats.shed == router.stats.rejected == 0


class TestRouterAutoscale:
    ACFG = AutoscaleConfig(min_replicas=1, max_replicas=3, window=2,
                           up_util=0.5, down_util=0.1, cooldown=0)

    def _loaded_router(self):
        reps = {}

        def factory(rid):
            reps[rid] = FakeReplica(slots=1, latency=4)
            return reps[rid]

        router = Router(factory, RouterConfig(
            replicas=1, queue_limit=64, replica_queue=1,
            autoscale=self.ACFG))
        return router, reps

    def test_scales_up_under_load_and_down_when_idle(self):
        router, reps = self._loaded_router()
        for _ in range(10):
            router.submit([1], max_new=2)
        done = router.run()
        assert len(done) == 10                  # nothing lost
        assert router.stats.scale_ups > 0
        assert router.stats.replica_peak > 1
        assert router.stats.replica_peak <= self.ACFG.max_replicas
        # idle the loop past a few windows: fleet shrinks back to min
        for _ in range(8):
            router.step()
        assert len(router.live_rids()) == 1
        assert router.stats.scale_downs > 0
        assert router.stats.retired > 0
        # trajectory is recorded every window and ends at min
        assert router.stats.replica_trajectory[-1] == 1
        assert max(router.stats.replica_trajectory) == router.stats.replica_peak

    def test_drain_before_retire_loses_no_request(self):
        """Force a scale-down while the victim replica still holds work:
        it must keep stepping (drain) and only then retire."""
        reps = {}

        def factory(rid):
            reps[rid] = FakeReplica(slots=1, latency=6)
            return reps[rid]

        router = Router(factory, RouterConfig(
            replicas=2, queue_limit=64,
            autoscale=AutoscaleConfig(min_replicas=1, max_replicas=2,
                                      window=1, up_util=2.0,  # never up
                                      down_util=1.0, cooldown=0)))
        for _ in range(2):
            router.submit([1], max_new=2)
        # both replicas busy; down_util=1.0 triggers a drain immediately
        done = router.run()
        assert len(done) == 2                   # drained, not dropped
        assert router.stats.scale_downs >= 1
        assert router.stats.retired >= 1
        assert len(router.replicas) == 1

    def test_scale_up_revives_draining_replica(self):
        built = []

        def factory(rid):
            built.append(rid)
            r = FakeReplica(slots=1, latency=99)
            return r

        router = Router(factory, RouterConfig(
            replicas=2, autoscale=AutoscaleConfig(
                min_replicas=1, max_replicas=2, window=1, cooldown=0)))
        router._draining.add(1)
        router.replicas[1].submit([1], 2, uid=50)   # keeps it pending
        # saturate replica 0 so the next window wants a scale-up
        router.replicas[0].submit([1], 2, uid=51)
        router.submit([1], max_new=2)
        router.step()                               # window=1: tick fires
        assert router.stats.scale_ups == 1
        assert 1 not in router._draining            # revived, not rebuilt
        assert built == [0, 1]                      # no third replica

    def test_initial_fleet_clamped_into_autoscale_bounds(self):
        router, reps = fake_router(
            n=1, autoscale=AutoscaleConfig(min_replicas=2, max_replicas=4))
        assert len(router.live_rids()) == 2


class TestRouterConfigValidation:
    def test_rejects_bad_values(self):
        with pytest.raises(ValueError, match="replicas"):
            RouterConfig(replicas=0)
        with pytest.raises(ValueError, match="queue_limit"):
            RouterConfig(queue_limit=0)
        with pytest.raises(ValueError, match="policy"):
            RouterConfig(policy="drop")
        with pytest.raises(ValueError, match="replica_queue"):
            RouterConfig(replica_queue=0)


# ------------------------------------------------- engine integration

def setup(arch="qwen3-0.6b"):
    cfg = registry.get(arch, smoke=True)
    params, _ = M.materialize_params(cfg, seed=0)
    return cfg, params


def make_prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (int(n),)).astype(np.int32)
            for n in lens]


def engine_factory(cfg, params, **ecfg_kw):
    kw = dict(slots=2, max_prompt_len=32, max_len=40, chunk=4)
    kw.update(ecfg_kw)

    def factory(rid):
        return InProcessReplica(ServeEngine(cfg, params, EngineConfig(**kw)))

    return factory


class TestRoutedTokenIdentity:
    @pytest.mark.parametrize("n_replicas", [1, 3])
    def test_routed_greedy_matches_single_engine(self, n_replicas):
        """The acceptance bar: the same fixed stream through the router
        (any replica count) and through one engine directly must emit
        identical greedy tokens per uid."""
        cfg, params = setup()
        prompts = make_prompts(cfg, [9, 17, 30, 12, 5, 21], seed=1)
        gen = 6
        single = ServeEngine(cfg, params, EngineConfig(
            slots=2, max_prompt_len=32, max_len=40, chunk=4))
        for p in prompts:
            single.submit(p, max_new=gen)
        base = {c.uid: c.tokens for c in single.run()}

        router = Router(engine_factory(cfg, params),
                        RouterConfig(replicas=n_replicas, queue_limit=64))
        for p in prompts:
            router.submit(p, max_new=gen)
        done = router.run()
        assert {c.uid: c.tokens for c in done} == base
        assert all(c.finish_reason == "length" for c in done)
        # queue split invariants hold on real completions
        for c in done:
            assert c.queue_s == pytest.approx(
                c.router_queue_s + c.engine_queue_s)
            assert c.latency_s >= c.queue_s >= 0.0

    @pytest.mark.parametrize("n_replicas", [1, 2])
    def test_routed_multicodebook_matches_single_engine(self, n_replicas):
        """Multi-codebook requests route through replicas for free: the
        router is token-plane-agnostic (prompts [S, K] survive its queue
        as K-tuples) and every replica is just an engine, so routed
        musicgen output must equal one engine's — at N=1 and N=2."""
        cfg, params = setup("musicgen-large")
        K = cfg.n_codebooks
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, cfg.vocab_size, (n, K)).astype(np.int32)
                   for n in (9, 14, 6, 11)]
        gen = 5
        single = ServeEngine(cfg, params, EngineConfig(
            slots=2, max_prompt_len=32, max_len=40, chunk=4))
        for p in prompts:
            single.submit(p, max_new=gen)
        base = {c.uid: c.tokens for c in single.run()}
        assert all(len(t) == K for c in base.values() for t in c)

        router = Router(engine_factory(cfg, params),
                        RouterConfig(replicas=n_replicas, queue_limit=64))
        for p in prompts:
            router.submit(p, max_new=gen)
        assert {c.uid: c.tokens for c in router.run()} == base

    def test_routed_sampling_placement_invariant(self):
        """temp>0 streams are keyed by router-global uid + token index,
        so WHICH replica serves a request cannot change its tokens."""
        cfg, params = setup()
        prompts = make_prompts(cfg, [9, 14, 11, 8], seed=2)
        gen = 6
        streams = {}
        for n in (1, 2):
            router = Router(engine_factory(cfg, params),
                            RouterConfig(replicas=n))
            for p in prompts:
                router.submit(p, max_new=gen, temperature=0.7)
            streams[n] = {c.uid: c.tokens for c in router.run()}
        assert streams[1] == streams[2]

    def test_backpressure_on_real_engines_accounts_everything(self):
        """Slot+page exhaustion through real engines: a tiny paged fleet
        with a tight router queue must complete or honestly shed every
        request — and complete them all when the queue is ample."""
        cfg, params = setup()
        prompts = make_prompts(cfg, [12] * 8, seed=3)
        gen = 4
        factory = engine_factory(cfg, params, slots=1, page_size=8)
        tight = Router(factory, RouterConfig(
            replicas=1, queue_limit=2, policy="shed", replica_queue=1))
        for p in prompts:
            tight.submit(p, max_new=gen)
        done = tight.run()
        st = tight.stats
        assert st.completed + st.shed == st.submitted == 8
        assert st.shed > 0                      # the queue really bound
        assert len(done) == 8                   # every uid has a record
        ample = Router(factory, RouterConfig(replicas=1, queue_limit=64))
        for p in prompts:
            ample.submit(p, max_new=gen)
        assert all(c.finish_reason == "length" for c in ample.run())
        assert ample.stats.shed == ample.stats.rejected == 0

    def test_engine_totals_aggregates_fleet(self):
        cfg, params = setup()
        prompts = make_prompts(cfg, [9, 13, 11, 7], seed=4)
        router = Router(engine_factory(cfg, params),
                        RouterConfig(replicas=2))
        for p in prompts:
            router.submit(p, max_new=4)
        router.run()
        total = router.engine_totals()
        assert total.prefill_requests == 4
        assert total.decode_steps > 0
        per_rep = [r.stats() for r in router.replicas.values()]
        assert total.decode_tokens == sum(s.decode_tokens for s in per_rep)
