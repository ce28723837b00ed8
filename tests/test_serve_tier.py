"""Serving tier: replica pool, admission, stealing, deadlines, hot-swap.

The tier contract (ISSUE 8): requests submitted by (codes, model name)
join the shortest replica queue, coalesce into same-model deadline-bucket
batches, and run under a registry lease — so admission bounds the backlog
(reject / shed-oldest), idle replicas steal from the deepest queue, and a
hot-swap under load never routes a request to a torn-down engine.  Fake
engines make each scenario deterministic; the final tests close the loop
with real jitted engines serving two models concurrently, bit-exactly.
"""

import threading
import time

import numpy as np
import pytest

from repro.serve.registry import ModelInfo, ModelRegistry, RegistryError
from repro.serve.scheduler import RejectedError, ServeConfig
from repro.serve.tier import ServeTier, TierConfig, TierStats


class EchoEngine:
    """Deterministic per-row transform; records what it served."""

    def __init__(self, tag=0, n_inputs=4):
        self.tag = tag
        self.n_inputs = n_inputs
        self.closed = False
        self.runs_after_close = 0
        self.calls = []               # batch sizes, in service order

    def run(self, x):
        if self.closed:
            self.runs_after_close += 1
        x = np.asarray(x, np.int64)
        self.calls.append(x.shape[0])
        return x * 10 + self.tag

    def close(self):
        self.closed = True


class GateEngine(EchoEngine):
    """Blocks every run() until released — freezes a replica mid-batch."""

    def __init__(self, tag=0, n_inputs=4):
        super().__init__(tag, n_inputs)
        self.release = threading.Event()

    def run(self, x):
        self.release.wait(timeout=30)
        return super().run(x)


def _tier(engine, *, n_replicas=1, steal=False, model="m", **serve_kw):
    reg = ModelRegistry()
    reg.register(model, engine)
    cfg = TierConfig(n_replicas=n_replicas, steal=steal, warmup=False,
                     serve=ServeConfig(max_batch=8, max_delay_ms=1.0,
                                       warmup=False, **serve_kw))
    return ServeTier(reg, cfg)


# --------------------------------------------------------------------------- #
# registry semantics
# --------------------------------------------------------------------------- #
def test_registry_publish_swap_and_lease_drain():
    reg = ModelRegistry()
    a, b = EchoEngine(1), EchoEngine(2)
    assert reg.register("m", a, content_hash="ha") == 1
    # idempotent republish of the same hash; clobber needs replace=True
    assert reg.register("m", a, content_hash="ha") == 2 - 1
    with pytest.raises(RegistryError, match="replace"):
        reg.register("m", b, content_hash="hb")
    assert "m" in reg and len(reg) == 1
    assert isinstance(reg.info("m"), ModelInfo)
    assert reg.info("m").content_hash == "ha"

    # a leased entry survives the swap until its lease drains
    lease = reg.acquire("m")
    assert reg.swap("m", b, content_hash="hb") == 2
    assert not a.closed and reg.draining() == 1
    lease_b = reg.acquire("m")
    assert lease_b.engine is b               # new submits see the new engine
    reg.release(lease_b)
    reg.release(lease)
    assert a.closed and reg.draining() == 0  # drained -> torn down

    reg.unregister("m")
    assert b.closed and "m" not in reg
    with pytest.raises(RegistryError):
        reg.acquire("m")
    with pytest.raises(RegistryError):
        reg.unregister("m")


# --------------------------------------------------------------------------- #
# submit validation + lifecycle
# --------------------------------------------------------------------------- #
def test_tier_submit_validates_model_and_shape():
    reg = ModelRegistry()
    reg.register("a", EchoEngine(1))
    reg.register("b", EchoEngine(2, n_inputs=6))
    tier = ServeTier(reg, TierConfig(n_replicas=1, warmup=False,
                                     serve=ServeConfig(warmup=False)))
    with pytest.raises(RuntimeError, match="not running"):
        tier.submit(np.zeros(4, np.int64), "a")
    with tier:
        with pytest.raises(ValueError, match="model= is required"):
            tier.submit(np.zeros(4, np.int64))      # ambiguous: 2 models
        with pytest.raises(RegistryError):
            tier.submit(np.zeros(4, np.int64), "nope")
        with pytest.raises(ValueError, match="codes"):
            tier.submit(np.zeros(3, np.int64), "a")  # wrong width
        f = tier.submit(np.arange(6, dtype=np.int64), "b")
        np.testing.assert_array_equal(f.result(timeout=10),
                                      np.arange(6) * 10 + 2)
    with pytest.raises(RuntimeError, match="already started"):
        with _tier(EchoEngine()) as t:
            t.start()


def test_single_model_needs_no_name():
    with _tier(EchoEngine(tag=3)) as tier:
        f = tier.submit(np.ones(4, np.int64))
        np.testing.assert_array_equal(f.result(timeout=10),
                                      np.ones(4) * 10 + 3)
    s = tier.stats()
    assert isinstance(s, TierStats)
    assert s.n_requests == 1 and s.per_model == {"m": 1}
    assert s.as_dict()["n_requests"] == 1


# --------------------------------------------------------------------------- #
# admission control
# --------------------------------------------------------------------------- #
def test_tier_rejects_at_admission_when_bounded():
    eng = GateEngine()
    tier = _tier(eng, max_queue=3, overload_policy="reject")
    with tier:
        admitted, rejected = [], 0
        for k in range(10):
            try:
                admitted.append((k, tier.submit(np.full(4, k, np.int64))))
            except RejectedError:
                rejected += 1
        assert rejected > 0 and len(admitted) >= 3
        eng.release.set()
        for k, f in admitted:
            np.testing.assert_array_equal(f.result(timeout=10),
                                          np.full(4, k * 10, np.int64))
    s = tier.stats()
    assert s.n_rejected == rejected and s.n_shed == 0
    assert s.n_requests == len(admitted)


def test_shed_oldest_fails_the_globally_oldest_future():
    eng = GateEngine()
    tier = _tier(eng, max_queue=3, overload_policy="shed-oldest")
    with tier:
        gate = tier.submit(np.zeros(4, np.int64))    # replica takes it, blocks
        time.sleep(0.05)                             # now in flight, not queued
        a = tier.submit(np.full(4, 1, np.int64))
        b = tier.submit(np.full(4, 2, np.int64))
        c = tier.submit(np.full(4, 3, np.int64))     # bound hit: sheds a
        with pytest.raises(RejectedError, match="shed"):
            a.result(timeout=10)
        eng.release.set()
        for f, v in ((gate, 0), (b, 2), (c, 3)):
            np.testing.assert_array_equal(f.result(timeout=10),
                                          np.full(4, v * 10, np.int64))
    s = tier.stats()
    assert s.n_shed == 1 and s.n_requests == 3


def test_shed_with_nothing_queued_rejects_the_newcomer():
    eng = GateEngine()
    tier = _tier(eng, max_queue=1, overload_policy="shed-oldest")
    with tier:
        gate = tier.submit(np.zeros(4, np.int64))
        time.sleep(0.05)         # in flight: pending=1 but every queue empty
        with pytest.raises(RejectedError, match="nothing left to shed"):
            tier.submit(np.ones(4, np.int64))
        eng.release.set()
        gate.result(timeout=10)


# --------------------------------------------------------------------------- #
# work stealing
# --------------------------------------------------------------------------- #
def test_idle_replica_steals_oldest_half_of_deepest_queue():
    class FirstCallSlowEngine(EchoEngine):
        def __init__(self):
            super().__init__()
            self._gate = threading.Event()

        def run(self, x):
            if not self._gate.is_set():
                self._gate.set()
                time.sleep(0.3)          # pin replica 0 on the first batch
            return super().run(x)

    eng = FirstCallSlowEngine()
    reg = ModelRegistry()
    reg.register("m", eng)
    cfg = TierConfig(n_replicas=2, steal=True, warmup=False,
                     serve=ServeConfig(max_batch=4, max_delay_ms=1.0,
                                       warmup=False))
    with ServeTier(reg, cfg) as tier:
        probe = tier.submit(np.zeros(4, np.int64), _replica=0)
        time.sleep(0.05)                 # replica 0 now blocked in run()
        futs = [tier.submit(np.full(4, k, np.int64), _replica=0)
                for k in range(1, 9)]    # all routed to the busy replica
        for k, f in enumerate(futs, start=1):
            np.testing.assert_array_equal(f.result(timeout=10),
                                          np.full(4, k * 10, np.int64))
        probe.result(timeout=10)
    s = tier.stats()
    # replica 1 raided replica 0's backlog instead of idling behind it
    assert s.n_stolen > 0
    assert s.per_replica_batches[1] > 0
    assert s.n_requests == 9


def test_steal_disabled_keeps_queues_pinned():
    tier = _tier(EchoEngine(), n_replicas=2, steal=False)
    with tier:
        futs = [tier.submit(np.full(4, k, np.int64), _replica=0)
                for k in range(6)]
        for k, f in enumerate(futs):
            np.testing.assert_array_equal(f.result(timeout=10),
                                          np.full(4, k * 10, np.int64))
    s = tier.stats()
    assert s.n_stolen == 0
    assert s.per_replica_batches[1] == 0


# --------------------------------------------------------------------------- #
# deadline buckets
# --------------------------------------------------------------------------- #
def test_soonest_deadline_bucket_is_served_first():
    order = []

    class OrderEngine(EchoEngine):
        def __init__(self, tag):
            super().__init__(tag)

        def run(self, x):
            order.append(self.tag)
            return super().run(x)

    gate = GateEngine(tag=0)
    reg = ModelRegistry()
    reg.register("gate", gate)
    reg.register("late", OrderEngine(1))
    reg.register("soon", OrderEngine(2))
    cfg = TierConfig(n_replicas=1, warmup=False,
                     serve=ServeConfig(max_batch=8, max_delay_ms=1.0,
                                       warmup=False))
    with ServeTier(reg, cfg) as tier:
        g = tier.submit(np.zeros(4, np.int64), "gate")
        time.sleep(0.05)                 # replica blocked; queue builds behind
        f_late = tier.submit(np.ones(4, np.int64), "late")   # no deadline
        time.sleep(0.01)                 # strictly later arrival...
        f_soon = tier.submit(np.ones(4, np.int64), "soon",
                             deadline_ms=5.0)                # ...sooner due
        gate.release.set()
        f_soon.result(timeout=10)
        f_late.result(timeout=10)
        g.result(timeout=10)
    # deadline-bucketed order beat FIFO: the due request jumped the queue
    assert order == [2, 1]
    assert tier.stats().n_requests == 3


def test_deadline_misses_are_counted():
    eng = GateEngine()
    with _tier(eng, slo_ms=1.0) as tier:       # every request dies its SLO
        f = tier.submit(np.zeros(4, np.int64))
        time.sleep(0.05)
        eng.release.set()
        f.result(timeout=10)
    assert tier.stats().deadline_misses == 1


# --------------------------------------------------------------------------- #
# hot-swap under load
# --------------------------------------------------------------------------- #
def test_hot_swap_under_load_never_serves_a_torn_down_engine():
    engines = [EchoEngine(tag) for tag in (1, 2, 3)]
    reg = ModelRegistry()
    reg.register("m", engines[0], content_hash="h1")
    cfg = TierConfig(n_replicas=2, warmup=False,
                     serve=ServeConfig(max_batch=8, max_delay_ms=0.5,
                                       warmup=False))
    results, stop = [], threading.Event()

    def hammer():
        x = np.ones(4, np.int64)
        while not stop.is_set():
            try:
                f = tier.submit(x, "m")
            except RuntimeError:
                break
            results.append(int(np.asarray(f.result(timeout=10))[0]))

    with ServeTier(reg, cfg) as tier:
        threads = [threading.Thread(target=hammer) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.1)
        reg.swap("m", engines[1], content_hash="h2")
        time.sleep(0.1)
        reg.swap("m", engines[2], content_hash="h3")
        time.sleep(0.1)
        stop.set()
        for t in threads:
            t.join()
    # every request was served by SOME registered version, never a dead one
    assert set(results) <= {11, 12, 13}
    assert {11, 13} <= set(results)          # both ends of the swap ran
    assert all(e.runs_after_close == 0 for e in engines)
    assert engines[0].closed and engines[1].closed and not engines[2].closed
    assert reg.draining() == 0
    assert tier.stats().per_model["m"] == len(results)


# --------------------------------------------------------------------------- #
# end to end: two real engines behind one tier
# --------------------------------------------------------------------------- #
def test_two_real_models_served_concurrently_bit_exact():
    import jax

    from repro.core.dais import compile_sequential
    from repro.core.lut_layers import LUTDense
    from repro.kernels.lut_serve import input_code_bounds
    from repro.serve.api import EngineSpec, build, tier_from_built

    def make(dims, seed):
        layers = [LUTDense(ci, co, hidden=4, use_batchnorm=(k == 0))
                  for k, (ci, co) in enumerate(zip(dims[:-1], dims[1:]))]
        keys = jax.random.split(jax.random.PRNGKey(seed), len(layers))
        return compile_sequential(
            layers, [l.init(k) for l, k in zip(layers, keys)], 4, 2)

    progs = {"a": make([6, 5, 3], 0), "b": make([4, 4], 1)}
    built = {n: build(p, EngineSpec(n_random=64)) for n, p in progs.items()}
    rng = np.random.default_rng(9)
    codes, refs = {}, {}
    for n, p in progs.items():
        lo, hi = input_code_bounds(p)
        codes[n] = rng.integers(lo, hi + 1, (24, len(lo)), np.int64)
        refs[n] = p.run(codes[n])

    tier = tier_from_built(
        built, TierConfig(n_replicas=2,
                          serve=ServeConfig(max_batch=8, max_delay_ms=1.0)),
        start=False)
    with tier:
        futs = [(n, k, tier.submit(codes[n][k], n))
                for k in range(24) for n in ("a", "b")]   # interleaved
        for n, k, f in futs:
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=60), np.int64), refs[n][k])
    s = tier.stats()
    assert s.per_model == {"a": 24, "b": 24}
    assert s.n_requests == 48 and s.n_batches >= 2
    # batches never mix models, so fills can't exceed the per-model counts
    assert s.mean_batch_fill <= 8


# --------------------------------------------------------------------------- #
# counters: seconds queued and seconds a flush takes
# --------------------------------------------------------------------------- #
class SlowEngine(EchoEngine):
    def run(self, x):
        time.sleep(0.002)
        return super().run(x)


def test_queue_and_flush_seconds_bound_the_tier_latency():
    tier = _tier(SlowEngine())
    s0 = tier.stats()
    assert (s0.queue_wait_s, s0.flush_s) == (0.0, 0.0)
    with tier:
        for k in range(12):          # one at a time: one request a batch
            tier.submit(np.full(4, k)).result(timeout=10)
    s = tier.stats()
    assert s.n_requests == s.n_batches == 12
    queue = s.queue_wait_s / s.n_requests
    flush = s.flush_s / s.n_batches
    mean_latency = float(np.mean(tier._latencies_s))
    assert 0 < queue <= mean_latency <= queue + flush
    assert flush >= 0.002            # the engine's sleep is inside the flush


class FailingEngine(EchoEngine):
    def run(self, x):
        raise RuntimeError("device lost")


def test_queue_and_flush_seconds_count_only_served_batches():
    tier = _tier(FailingEngine())
    with tier:
        fut = tier.submit(np.zeros(4))
        with pytest.raises(RuntimeError, match="device lost"):
            fut.result(timeout=10)
    s = tier.stats()
    assert (s.n_requests, s.n_batches) == (0, 0)
    assert (s.queue_wait_s, s.flush_s) == (0.0, 0.0)
