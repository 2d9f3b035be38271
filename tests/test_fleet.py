"""repro.fleet: replica health machine, router placement/hedging/
failover, and fleet-level oracle parity across a replica crash.

Host-only fakes drive the router logic (virtual clocks, deterministic
token streams); the device-backed tests at the bottom assert the real
property — greedy token identity to the B=1 oracle for requests migrated
across a mid-serving replica kill — and the per-engine metrics isolation
of two live engines sharing one Registry."""
import collections
import dataclasses

import numpy as np
import pytest

from repro.fleet import DEGRADED, DOWN, HEALTHY, EngineReplica, Router
from repro.obs import Obs
from repro.serve.engine import Request
from repro.serve.faults import FaultConfig, FaultInjector

# deterministic stream fakes emit: token i is always 100 + i, so any
# migrated/hedged/resumed request that finishes must carry exactly the
# prefix-closed stream — a host-only analogue of oracle parity
def _stream(n):
    return [100 + i for i in range(n)]


def req(rid, new=4, prompt_len=4, deadline_s=None, priority=0):
    return Request(prompt=np.arange(prompt_len, dtype=np.int32) + 1,
                   max_new_tokens=new, id=rid, deadline_s=deadline_s,
                   priority=priority)


# ---------------------------------------------------------------------------
# Fakes: a host-only engine (health tests) and replica (router tests)
# ---------------------------------------------------------------------------
class FakeEngine:
    """The slice of ContinuousEngine that EngineReplica touches."""

    def __init__(self):
        self.obs = Obs()
        self.anomalies = 0
        self._results = {}
        self._traces = {}
        self.step_fn = lambda: True
        self.max_seq = None

        class _Sched:
            queue_depth = 0
            running = ()
            queue = collections.deque()

            def drain_doomed(self):
                return []

            def close_intake(self):
                pass

        self.scheduler = _Sched()

    def step(self):
        return self.step_fn()

    def stats(self):
        return {}


class FakeReplica:
    """Host-only replica honouring the Router's interface.

    One token per step per running job; ``capacity`` running slots and a
    ``max_queue``-bounded wait queue (a full queue refuses the submit,
    like the real engine's bounded intake).  ``stalled`` replicas admit
    but never emit — hedge bait."""

    def __init__(self, name, capacity=2, max_queue=8, stalled=False):
        self.name = name
        self.state = HEALTHY
        self.salvaged = False
        self.capacity = capacity
        self.max_queue = max_queue
        self.stalled = stalled
        self._next = 0
        self.jobs = {}                 # local order -> job dict
        self.run = []                  # local orders occupying slots
        self.wait = []                 # local orders queued
        self.results = {}
        self.cancels = 0

    @property
    def live(self):
        return self.state != DOWN

    @property
    def load(self):
        return len(self.jobs)

    @property
    def max_seq(self):
        return None

    def submit(self, request, arrival_s=0.0, resume_tokens=None,
               preemptions=0):
        if not self.live:
            return -1, False
        local = self._next
        self._next += 1
        if len(self.wait) >= self.max_queue:
            return local, False        # bounded intake: transient refusal
        self.jobs[local] = {"req": request,
                            "tokens": list(resume_tokens or []),
                            "resume0": len(resume_tokens or []),
                            "budget": request.max_new_tokens,
                            "preempts": preemptions}
        self.wait.append(local)
        return local, True

    def step(self):
        if not self.live:
            return False
        progress = False
        while self.wait and len(self.run) < self.capacity:
            self.run.append(self.wait.pop(0))
            progress = True
        if self.stalled:
            return progress
        for local in list(self.run):
            job = self.jobs[local]
            job["tokens"].append(100 + len(job["tokens"]))
            progress = True
            if len(job["tokens"]) >= job["budget"]:
                self._finish(local, "FINISHED_BUDGET")
        return progress

    def _finish(self, local, status):
        job = self.jobs.pop(local)
        if local in self.run:
            self.run.remove(local)
        if local in self.wait:
            self.wait.remove(local)
        served = len(job["tokens"]) > job["resume0"] or status.startswith(
            "FINISHED")
        self.results[local] = {
            "id": job["req"].id, "tokens": list(job["tokens"]),
            "decode_len": len(job["tokens"]), "status": status,
            "preemptions": job["preempts"], "tokens_per_s": 0.0,
            "prefill_s": 0.0 if served else None, "decode_s": 0.0,
            "queue_s": 0.0, "latency_s": 0.0,
        }

    def result(self, local, pop=False):
        return self.results.pop(local, None) if pop \
            else self.results.get(local)

    def cancel(self, request_id):
        if not self.live:
            return False
        for local, job in list(self.jobs.items()):
            if job["req"].id == request_id:
                self.cancels += 1
                self._finish(local, "CANCELLED")
                return True
        return False

    def first_token_seen(self, local):
        job = self.jobs.get(local)
        if job is not None:
            return len(job["tokens"]) > job["resume0"]
        return local in self.results

    def drain(self):
        self.stalled = False
        while self.jobs:
            self.step()
        return []

    def force_crash(self, reason="forced crash"):
        self.state = DOWN

    def salvage(self):
        from repro.fleet import LostRequest, Salvage
        if self.state != DOWN:
            raise RuntimeError("salvage on a live fake")
        if self.salvaged:
            return Salvage({}, [])
        self.salvaged = True
        results, self.results = self.results, {}
        lost = [LostRequest(job["req"], list(job["tokens"]),
                            job["preempts"], local)
                for local, job in sorted(self.jobs.items())]
        self.jobs.clear()
        self.run, self.wait = [], []
        return Salvage(results, lost)

    def stats(self):
        return {"name": self.name, "state": self.state}


# ---------------------------------------------------------------------------
# EngineReplica health machine (fake engine, fake clock)
# ---------------------------------------------------------------------------
def _ticking_clock(step):
    t = [0.0]

    def clock():
        t[0] += step
        return t[0]
    return clock


def test_health_step_timeout_degrades_then_downs():
    rep = EngineReplica("r0", FakeEngine(), step_timeout_s=1.0,
                        down_after=3, clock=_ticking_clock(1.1))
    rep.step()                         # 1.2s elapsed between t0 and t1
    assert rep.state == DEGRADED and rep.consecutive_timeouts == 1
    rep.step()
    assert rep.state == DEGRADED
    rep.step()
    assert rep.state == DOWN and "hung" in rep.down_reason
    assert rep.engine.obs.registry.value("replica.step_timeouts") == 3
    assert rep.engine.obs.registry.value("replica.health") == 2.0
    assert not rep.step()              # DOWN replicas are inert


def test_health_step_that_compiles_is_not_a_timeout():
    """A step in which the engine compiled a new device program (the first
    dispatch of a prefill bucket or of the decode loop) is slow, not hung:
    it neither degrades nor downs the replica; the next slow step without a
    compile does."""
    eng = FakeEngine()
    eng.programs_compiled = 0

    def compile_one():
        eng.programs_compiled += 1
        return True
    eng.step_fn = compile_one
    rep = EngineReplica("r0", eng, step_timeout_s=1.0, down_after=1,
                        clock=_ticking_clock(1.1))
    for _ in range(3):
        rep.step()
    assert rep.state == HEALTHY and rep.consecutive_timeouts == 0
    eng.step_fn = lambda: True
    rep.step()
    assert rep.state == DOWN and "hung" in rep.down_reason


def test_health_anomaly_degrades_and_recovers():
    eng = FakeEngine()
    rep = EngineReplica("r0", eng, step_timeout_s=10.0, recover_after=2,
                        clock=_ticking_clock(0.001))
    rep.step()
    assert rep.state == HEALTHY
    eng.anomalies = 2                  # NaN guard tripped since last step
    rep.step()
    assert rep.state == DEGRADED
    rep.step()                         # clean step 1
    assert rep.state == DEGRADED
    rep.step()                         # clean step 2 -> recovered
    assert rep.state == HEALTHY
    assert rep.engine.obs.registry.value("replica.health") == 0.0


def test_health_engine_exception_is_a_crash():
    eng = FakeEngine()
    eng.step_fn = lambda: (_ for _ in ()).throw(RuntimeError("device lost"))
    rep = EngineReplica("r0", eng)
    assert not rep.step()
    assert rep.state == DOWN and "device lost" in rep.down_reason
    assert rep.engine.obs.registry.value("replica.crashes") == 1
    assert rep.submit(req(0)) == (-1, False)
    assert not rep.cancel(0) and rep.drain() == []


def test_health_injected_crash_and_hang_faults():
    inj = FaultInjector(FaultConfig(seed=0, crash_p=1.0))
    rep = EngineReplica("r0", FakeEngine(), faults=inj)
    rep.step()
    assert rep.state == DOWN and rep.down_reason == "injected crash"
    assert inj.stats()["crashes"] == 1

    inj2 = FaultInjector(FaultConfig(seed=0, hang_p=1.0, hang_s=0.01))
    rep2 = EngineReplica("r1", FakeEngine(), faults=inj2,
                         step_timeout_s=0.001, down_after=100)
    rep2.step()                        # real clock: the sleep IS the stall
    assert rep2.state == DEGRADED and rep2.consecutive_timeouts == 1
    assert inj2.stats()["hangs"] == 1


def test_salvage_only_when_down_and_exactly_once():
    import types
    eng = FakeEngine()
    rep = EngineReplica("r0", eng)
    with pytest.raises(RuntimeError, match="only DOWN"):
        rep.salvage()
    # unconsumed result + one queued entry + one running slot
    eng._results[0] = {"status": "FINISHED_BUDGET", "id": 0}
    eng.scheduler.queue.append(types.SimpleNamespace(
        request=req(1), resume_tokens=[7], preemptions=1, order=1))
    eng.scheduler.running = (types.SimpleNamespace(
        request=req(2), tokens=[5, 6], preemptions=0, order=2),)
    rep.force_crash("test kill")
    salvage = rep.salvage()
    assert set(salvage.results) == {0}
    assert [(l.local_order, l.resume_tokens) for l in salvage.lost] == \
        [(1, [7]), (2, [5, 6])]
    again = rep.salvage()              # idempotent: second call is empty
    assert not again.results and not again.lost


# ---------------------------------------------------------------------------
# Router: placement, retry, shedding (host fakes, virtual clock)
# ---------------------------------------------------------------------------
def _router(reps, **kw):
    now = [0.0]
    kw.setdefault("clock", lambda: now[0])
    return Router(reps, **kw), now


def test_jsq_places_on_least_loaded():
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    r0.submit(req(100)), r0.submit(req(101))     # preload r0
    router, _ = _router([r0, r1])
    router.submit(req(0))
    assert any(j["req"].id == 0 for j in r1.jobs.values())
    assert not any(j["req"].id == 0 for j in r0.jobs.values())


def test_round_robin_rotates():
    r0, r1 = FakeReplica("r0", max_queue=8), FakeReplica("r1", max_queue=8)
    router, _ = _router([r0, r1], policy="round_robin")
    for i in range(4):
        router.submit(req(i))
    assert len(r0.jobs) == 2 and len(r1.jobs) == 2


def test_retry_backoff_then_placement():
    rep = FakeReplica("r0", max_queue=0)         # refuses everything
    router, now = _router([rep], backoff_base_s=0.01, backoff_cap_s=0.1)
    router.submit(req(0))
    st = router.stats()
    assert st["pending_depth"] == 1 and st["place_retries"] >= 1
    rep.max_queue = 4                            # pressure clears
    now[0] += 1.0                                # past any backoff
    router.step()
    assert router.stats()["pending_depth"] == 0
    assert any(j["req"].id == 0 for j in rep.jobs.values())


def test_overflow_sheds_lowest_priority_youngest():
    rep = FakeReplica("r0", max_queue=0)
    router, _ = _router([rep], max_pending=2)
    o_hi = router.submit(req(0, priority=5))
    o_mid = router.submit(req(1, priority=3))
    o_lo = router.submit(req(2, priority=0))     # overflow: shed the lowest
    res = router.result(o_lo)
    assert res is not None and res["status"] == "REJECTED"
    assert router.result(o_hi) is None and router.result(o_mid) is None
    assert router.stats()["shed"]["overflow"] == 1


def test_deadline_doomed_pending_is_shed():
    rep = FakeReplica("r0", max_queue=0)
    router, now = _router([rep])
    order = router.submit(req(0, deadline_s=0.1), arrival_s=0.0)
    assert router.result(order) is None
    now[0] += 1.0
    router.step()
    res = router.result(order)
    assert res["status"] == "REJECTED" and res["replica"] is None
    assert router.stats()["shed"]["deadline"] == 1


def test_all_replicas_down_fails_pending():
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    r0.force_crash(), r1.force_crash()
    router, _ = _router([r0, r1])
    order = router.submit(req(0))
    assert router.result(order)["status"] == "FAILED"
    assert router.stats()["shed"]["no_live_replicas"] == 1


def test_cancel_pending_and_placed():
    slow = FakeReplica("r0", max_queue=0)
    router, _ = _router([slow])
    order = router.submit(req(0))                # unplaceable -> pending
    assert router.cancel(0)
    assert router.result(order)["status"] == "CANCELLED"
    slow.max_queue = 4
    order1 = router.submit(req(1, new=8))
    router.step()
    assert router.cancel(1)                      # lives on the replica now
    router.step()                                # collect the terminal
    assert router.result(order1)["status"] == "CANCELLED"


def test_closed_intake_rejects_immediately():
    router, _ = _router([FakeReplica("r0")])
    router.intake_closed = True
    order = router.submit(req(0))
    res = router.result(order)
    assert res["status"] == "REJECTED" and res["migrations"] == 0
    # a closed-intake reject is not a shed (no reason counted)
    assert all(v == 0 for v in router.stats()["shed"].values())


def test_submit_rejects_prompt_over_fleet_max_seq():
    class _Bounded(FakeReplica):
        @property
        def max_seq(self):
            return 8

    router, _ = _router([_Bounded("r0")])
    with pytest.raises(ValueError, match="max_seq"):
        router.submit(req(0, prompt_len=12))


# ---------------------------------------------------------------------------
# Router: hedging and failover (host fakes)
# ---------------------------------------------------------------------------
def test_hedge_fires_and_first_winner_settles_once():
    slow = FakeReplica("r0", stalled=True)       # admits, never emits
    fast = FakeReplica("r1")
    router, now = _router([slow, fast], hedge_after_s=0.1)
    order = router.submit(req(0, new=3))
    router.step()                                # placed on r0 (name tie)
    assert any(j["req"].id == 0 for j in slow.jobs.values())
    now[0] += 0.5
    router.step()                                # past threshold -> hedge
    st = router.stats()
    assert st["hedges"] == 1
    for _ in range(6):
        router.step()
    res = router.result(order)
    assert res is not None and res["status"] == "FINISHED_BUDGET"
    assert res["replica"] == "r1" and res["tokens"] == _stream(3)
    assert router.stats()["hedge_wins"] == {"primary": 0, "hedge": 1}
    assert sum(router.terminal_counts().values()) == 1   # settled ONCE
    assert slow.cancels == 1                     # loser leg cancelled
    assert not slow.results and not router._zombies      # zombie drained


def test_hedge_waits_for_ttft_samples_when_adaptive():
    slow = FakeReplica("r0", stalled=True)
    fast = FakeReplica("r1")
    router, now = _router([slow, fast], hedge_min_samples=8)
    router.submit(req(0))
    now[0] += 100.0
    router.step()                                # no p99 yet -> no hedge
    assert router.stats()["hedges"] == 0


def test_failover_migrates_with_resume_and_stream_is_identical():
    r0, r1 = FakeReplica("r0", capacity=1), FakeReplica("r1", capacity=1)
    router, _ = _router([r0, r1])
    order_a = router.submit(req(0, new=6))       # -> r0 (name tie)
    order_b = router.submit(req(1, new=2))       # -> r1 (jsq)
    router.step()
    router.step()                                # A has 2 tokens on r0
    job_a = next(iter(r0.jobs.values()))
    assert job_a["tokens"] == _stream(2)
    r0.force_crash()
    guard = 0
    while router.result(order_a) is None or router.result(order_b) is None:
        guard += 1
        assert guard < 50, "failover did not converge"
        router.step()
    res_a = router.result(order_a)
    assert res_a["status"] == "FINISHED_BUDGET"
    assert res_a["replica"] == "r1" and res_a["migrations"] == 1
    assert res_a["tokens"] == _stream(6)         # resumed, not restarted
    st = router.stats()
    assert st["failovers"] == 1 and st["migrated_requests"] == 1
    assert router.result(order_b)["status"] == "FINISHED_BUDGET"
    assert sum(router.terminal_counts().values()) == 2


def test_failover_surfaces_salvaged_terminal_results():
    r0, r1 = FakeReplica("r0"), FakeReplica("r1", max_queue=0)
    router, _ = _router([r0, r1])
    order = router.submit(req(0, new=1))
    r0.step()                                    # finishes INSIDE the replica
    assert r0.results                            # ...unconsumed by the router
    r0.force_crash()
    router.step()                                # failover surfaces it
    res = router.result(order)
    assert res["status"] == "FINISHED_BUDGET" and res["replica"] == "r0"
    assert res["tokens"] == _stream(1)
    assert sum(router.terminal_counts().values()) == 1


def test_generate_over_fakes_orders_results():
    reps = [FakeReplica("r0"), FakeReplica("r1")]
    router = Router(reps, seed=0)
    reqs = [req(i, new=2 + i % 3) for i in range(6)]
    results = router.generate(reqs)
    assert [r["id"] for r in results] == list(range(6))
    assert all(r["status"] == "FINISHED_BUDGET" for r in results)
    assert all(r["tokens"] == _stream(len(r["tokens"])) for r in results)


# ---------------------------------------------------------------------------
# Device-backed: metrics isolation + failover oracle parity
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_setup():
    import jax
    from repro.configs.registry import get_smoke_config
    from repro.models.registry import build_model
    cfg = get_smoke_config("tinyllama-1.1b").replace(dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _tiny_reqs(specs, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(prompt=rng.randint(1, 500, size=s).astype(np.int32),
                    max_new_tokens=n, id=i)
            for i, (s, n) in enumerate(specs)]


def test_two_live_engines_metrics_isolation(tiny_setup):
    """Two engines share one Registry through scoped views: every series
    carries its replica label, per-engine stats() stay disjoint, and the
    shared TraceStore keeps same-order traces apart."""
    from repro.serve.engine import ContinuousEngine
    cfg, params = tiny_setup
    root = Obs()
    engs = [ContinuousEngine(cfg, params, max_slots=2, max_seq=32,
                             page_size=4, decode_chunk=4,
                             obs=root.scoped(replica=f"e{i}"))
            for i in range(2)]
    reqs = _tiny_reqs([(8, 3), (10, 4), (9, 2), (12, 5)])
    orders = [[], []]
    for i, eng in enumerate(engs):
        for r in reqs[2 * i:2 * i + 2]:
            orders[i].append(eng.submit(r))
    while not all(e.scheduler.idle for e in engs):   # both LIVE at once
        for eng in engs:
            eng.step()
    reg = root.registry
    for i, eng in enumerate(engs):
        assert reg.value("sched.submitted", replica=f"e{i}") == 2
        assert reg.value("sched.retired", replica=f"e{i}") == 2
        assert eng.stats()["retired"] == 2           # reads its own scope
    with pytest.raises(KeyError):
        reg.value("sched.submitted")                 # no unlabelled bleed
    for fname, _ in reg.items():
        if fname.startswith(("sched.", "engine.", "pool.", "trace.")):
            assert "replica=" in fname, fname
    done = list(root.traces.completed)
    assert len(done) == 4
    assert {t.replica for t in done} == {"e0", "e1"}
    by = {(t.replica, t.order) for t in done}
    assert by == {("e0", 0), ("e0", 1), ("e1", 0), ("e1", 1)}


def test_fleet_failover_oracle_parity(tiny_setup):
    """Kill a replica mid-serving; every finished request — including the
    migrated ones — must be token-identical to its B=1 oracle."""
    from repro.serve.engine import ContinuousEngine, Engine
    cfg, params = tiny_setup
    reqs = _tiny_reqs([(12, 10), (10, 12), (14, 9), (9, 11), (11, 10),
                       (13, 8)])
    oracle = Engine(cfg, params, max_batch=1, max_seq=32)
    want = [oracle.generate([r])[0]["tokens"] for r in reqs]
    root = Obs()
    pool = [EngineReplica(
        f"r{i}", ContinuousEngine(cfg, params, max_slots=2, max_seq=32,
                                  page_size=4, decode_chunk=3,
                                  obs=root.scoped(replica=f"r{i}")))
        for i in range(2)]
    router = Router(pool, seed=0, obs=root)
    orders = [router.submit(r) for r in reqs]
    victim = pool[0]
    free0 = {rep.name: rep.engine.block_table.allocator.available
             for rep in pool}
    killed, guard = False, 0
    while any(router.result(o) is None for o in orders):
        guard += 1
        assert guard < 5000, "fleet run did not converge"
        router.step()
        if not killed and any(s.tokens
                              for s in victim.engine.scheduler.running):
            victim.force_crash("test kill")
            killed = True
    assert killed and victim.salvaged
    results = [router.result(o) for o in orders]
    migrated = [r for r in results if r["migrations"] > 0]
    assert migrated, "nothing migrated across the kill"
    for res, toks in zip(results, want):
        assert res["status"] in ("FINISHED_EOS", "FINISHED_BUDGET"), res
        assert res["tokens"] == toks, (res, toks)
    survivor = pool[1]
    assert survivor.engine.block_table.allocator.available == \
        free0[survivor.name]
    assert survivor.engine.scheduler.tokens_in_flight == 0
    assert sum(router.terminal_counts().values()) == len(reqs)
