"""Serverless invocation subsystem (repro/serverless/): stateless
payloads, action aggregation, warm-container affinity, retry/speculation
exactly-once effects, and the inline == fleet bitwise contract across all
four forecasters."""
import functools
import threading

import numpy as np
import pytest

from repro.core import Castor, ModelDeployment, Schedule
from repro.core.executor import FleetExecutor
from repro.forecast import (ANNForecaster, GAMForecaster, LSTMForecaster,
                            LinearForecaster)
from repro.serverless import (InlineBackend, InvocationPayload,
                              ProcessBackend, ServerlessExecutor)
from repro.serverless.backend import InvocationError
from repro.serverless.payload import JobRef, VersionRef
from repro.testing import FLEET_NOW as NOW, HOUR, build_steady_castor

DAY = 86400.0

MODELS = {
    "lr": (LinearForecaster, {}),
    "gam": (GAMForecaster, {}),
    "ann": (ANNForecaster, {"hidden": 16, "epochs": 30}),
    "lstm": (LSTMForecaster, {"hidden": 8, "epochs": 30}),
}


# ------------------------------------------------------------ equivalence
@pytest.mark.parametrize("kind", list(MODELS))
def test_inline_serverless_equals_fleet_bitwise(kind):
    """Acceptance: tick(executor="serverless") with the inline backend is
    BITWISE identical to the fleet executor for all four forecasters,
    over several polls (cold build + warm ring updates), because bins are
    never split across invocations and each worker runs the exact fleet
    code path."""
    cls, hp = MODELS[kind]
    polls = 3
    ca = build_steady_castor(kind, cls, hp, n=4)
    cb = build_steady_castor(kind, cls, hp, n=4)
    for k in range(polls):
        ra = ca.tick(NOW + k * HOUR, executor="fleet")
        rb = cb.tick(NOW + k * HOUR, executor="serverless")
        assert ra and all(r.ok for r in ra), \
            [r.error for r in ra if not r.ok]
        assert rb and all(r.ok for r in rb), \
            [r.error for r in rb if not r.ok]
    for i in range(4):
        fa = ca.predictions.history(f"s-Z_PRO_0_{i}")
        fb = cb.predictions.history(f"s-Z_PRO_0_{i}")
        assert len(fa) == len(fb) == polls
        for x, y in zip(fa, fb):
            assert np.array_equal(x.times, y.times)
            assert np.array_equal(x.values, y.values), \
                (i, float(np.max(np.abs(x.values - y.values))))
    # telemetry surfaced through Castor.stats()
    s = cb.stats()["serverless"]
    assert s["invocations"] >= polls
    assert s["cold_starts"] >= 1 and s["warm_starts"] >= polls - 1


def test_bins_stay_whole_across_invocations():
    """Aggregation packs WHOLE bins: a catch-up cycle with several bins
    and a small aggregation factor must never split one bin's jobs across
    two invocations (bitwise megabatch numerics depend on it)."""
    c = build_steady_castor("lr", LinearForecaster, {}, n=6)
    ex = ServerlessExecutor(c, n_workers=2, aggregation=12,
                            speculative=False)
    c._serverless_ex = ex
    res = ex.run(c.scheduler.poll(NOW))
    assert all(r.ok for r in res)
    # 3h stall: 3 catch-up score bins of 6 jobs each; aggregation=12
    # packs two whole bins per action and the third alone — never a
    # partial bin
    res = ex.run(c.scheduler.poll(NOW + 3 * HOUR))
    assert len(res) == 18 and all(r.ok for r in res), \
        [r.error for r in res if not r.ok]
    recs = ex.monitor.records
    assert all(r["jobs"] % 6 == 0 for r in recs), recs   # whole bins only
    assert any(r["jobs"] == 12 and r["bins"] == 2 for r in recs), \
        recs                                             # aggregation real
    # catch-up forecasts persist at their own boundaries
    assert [f.created_at for f in c.predictions.history("s-Z_PRO_0_0")] \
        == [NOW + k * HOUR for k in range(4)]
    for f in c.predictions.history("s-Z_PRO_0_0"):
        assert f.times[0] == f.created_at


def test_sticky_affinity_keeps_bins_on_one_warm_worker():
    """Successive polls of one logical bin hit the same worker, whose
    FleetRuntime then advances O(delta) (warm loads) instead of cold
    rebuilding."""
    polls = 4
    c = build_steady_castor("lr", LinearForecaster, {}, n=4)
    ex = ServerlessExecutor(c, n_workers=3, speculative=False)
    c._serverless_ex = ex
    for k in range(polls):
        res = ex.run(c.scheduler.poll(NOW + k * HOUR))
        assert res and all(r.ok for r in res)
    workers = {r["worker"] for r in ex.monitor.records}
    assert len(workers) == 1            # one bin -> one sticky worker
    s = ex.stats()
    assert s["cold_starts"] == 1
    assert s["warm_starts"] == s["invocations"] - 1
    (w,) = [ex.backend._workers[w] for w in workers]
    assert w.executor.runtime.warm_loads >= polls - 2
    assert w.executor.runtime.cold_loads == 1


# ------------------------------------------------------------ resilience
class _FlakyBackend(InlineBackend):
    """Fails each invocation's first delivery at the backend level."""

    def __init__(self, system, *, n_workers=2, fail_first=1):
        super().__init__(system, n_workers=n_workers)
        self.fail_first = fail_first
        self.seen = {}
        self._seen_lock = threading.Lock()

    def invoke(self, payload, worker_id):
        with self._seen_lock:
            n = self.seen.get(payload.invocation_id, 0)
            self.seen[payload.invocation_id] = n + 1
        if n < self.fail_first:
            raise InvocationError("transient backend failure")
        return super().invoke(payload, worker_id)


def test_invoker_retries_with_backoff_exactly_once_effects():
    c = build_steady_castor("lr", LinearForecaster, {}, n=4)
    ex = ServerlessExecutor(c, backend=_FlakyBackend(c, n_workers=2),
                            max_retries=2, backoff_base_s=0.01,
                            speculative=False)
    res = ex.run(c.scheduler.poll(NOW))
    assert res and all(r.ok for r in res), \
        [r.error for r in res if not r.ok]
    s = ex.stats()
    assert s["retries"] >= 1 and s["failed_invocations"] >= 1
    # exactly-once effects despite at-least-once invocation
    for i in range(4):
        assert len(c.predictions.history(f"s-Z_PRO_0_{i}")) == 1
        assert len(c.versions.history(f"s-Z_PRO_0_{i}")) == 1
    # no spurious re-fire queued
    assert not c.scheduler.poll(NOW + 1.0)


def test_invoker_exhausted_retries_fail_and_requeue():
    c = build_steady_castor("lr", LinearForecaster, {}, n=2)
    ex = ServerlessExecutor(c, backend=_FlakyBackend(c, n_workers=2,
                                                     fail_first=99),
                            max_retries=1, backoff_base_s=0.01,
                            speculative=False)
    res = ex.run(c.scheduler.poll(NOW))
    assert res and not any(r.ok for r in res)
    # at-least-once: every occurrence re-fires at its own boundary
    refire = c.scheduler.poll(NOW + 1.0)
    assert sorted({j.task for j in refire}) == ["score", "train"]
    assert all(j.scheduled_at == NOW for j in refire)


def test_duplicate_invocation_is_idempotent():
    """A speculative backup / replayed action re-executing the same
    payload must not double-persist (the exactly-once argument)."""
    c = build_steady_castor("lr", LinearForecaster, {}, n=3)
    ex = ServerlessExecutor(c, n_workers=2, speculative=False)
    res = ex.run(c.scheduler.poll(NOW))
    assert all(r.ok for r in res)
    backend = ex.backend
    jobs = c.scheduler.poll(NOW + HOUR)
    refs = tuple(JobRef.from_job(j) for j in jobs)
    payload = InvocationPayload(invocation_id="dup-1", jobs=refs)
    r1 = backend.invoke(payload, "w0")
    r2 = backend.invoke(payload, "w1")       # the duplicate delivery
    assert all(o.ok for o in r1.outcomes + r2.outcomes)
    for i in range(3):
        assert len(c.predictions.history(f"s-Z_PRO_0_{i}")) == 2


def test_missing_version_fails_alone():
    """Serverless mirrors FleetExecutor's partial-bin semantics: a
    never-trained deployment fails alone, the rest of its bin scores."""
    c = build_steady_castor("lr", LinearForecaster, {}, n=4)
    c.deploy(ModelDeployment(
        name="cold", package="lr", signal="ENERGY_LOAD",
        entity="Z_PRO_0_0", train=None, score=Schedule(NOW, 1e12),
        user_params={"train_window_days": 14}))
    ex = ServerlessExecutor(c, n_workers=2, speculative=False)
    res = ex.run(c.scheduler.poll(NOW))
    by_name = {r.job.deployment_name: r for r in res
               if r.job.task == "score"}
    assert not by_name["cold"].ok
    assert "no trained version" in by_name["cold"].error
    assert all(r.ok for n, r in by_name.items() if n != "cold")
    refire = c.scheduler.poll(NOW + 1.0)
    assert [j.deployment_name for j in refire] == ["cold"]


# ------------------------------------------------------------ payloads
def test_payload_and_result_roundtrip_json_bitwise():
    job = JobRef("d0", "lr", "1.0", "score", NOW, "ENERGY_LOAD", "E0",
                 "params-key")
    arrs = {"w": np.linspace(-1, 1, 7).astype(np.float32),
            "b": np.arange(4, dtype=np.float64) * np.pi}
    vr = VersionRef("d0", 3, NOW - HOUR,
                    model_object={"kind": "lr", "params": arrs,
                                  "y_scale": 2.5})
    p = InvocationPayload(invocation_id="inv-1", jobs=(job,),
                          versions=(vr,), created_at=123.25, attempt=2)
    q = InvocationPayload.from_json(p.to_json())
    assert q.jobs == (job,)
    assert q.invocation_id == "inv-1" and q.attempt == 2
    mo = q.versions[0].model_object
    for k, v in arrs.items():
        got = mo["params"][k]
        assert got.dtype == v.dtype and np.array_equal(got, v)
    assert mo["y_scale"] == 2.5
    assert q.jobs[0].to_job().bin_key == job.to_job().bin_key


# ------------------------------------------------------------ process
def _mini_castor():
    """Cheapest possible picklable system factory: spawn-handshake tests
    only need the worker process to come up, not to model anything."""
    return Castor()


def test_process_backend_workers_reaped_on_gc():
    """Regression: a ProcessBackend leaked by a crashed invoker (or a
    test failing mid-run) used to orphan its spawned workers for the
    rest of the session. The weakref.finalize teardown must kill them
    when the backend object is collected — and at interpreter exit."""
    import gc
    be = ProcessBackend(_mini_castor, n_workers=1)
    (proc, _tq, _rq), _lock = be._worker("p0")     # force the spawn
    assert proc.is_alive()
    del be                                          # "crash": no close()
    gc.collect()
    proc.join(timeout=10.0)
    assert not proc.is_alive(), "orphaned worker survived backend GC"


def test_process_backend_context_manager_reaps_and_cleans_storage():
    import os
    with ProcessBackend(_mini_castor, n_workers=1) as be:
        (proc, _tq, _rq), _lock = be._worker("p0")
        root = be.storage.root                      # owned "auto" bucket
        assert proc.is_alive() and os.path.isdir(root)
    proc.join(timeout=10.0)
    assert not proc.is_alive()
    assert not os.path.exists(root)                 # owned bucket removed
    be.close()                                      # idempotent


def test_process_backend_refuses_when_parent_holds_tpu(monkeypatch):
    """A worker that needs the TPU this process holds would wait out the
    whole spawn timeout: the backend must refuse at once and start no
    process — unless the workers are pinned to the CPU."""
    import jax
    import multiprocessing
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    Process = multiprocessing.get_context("spawn").Process
    start, started = Process.start, []
    monkeypatch.setattr(Process, "start",
                        lambda self: started.append(self) or start(self))
    with ProcessBackend(_mini_castor, n_workers=1) as be:
        with pytest.raises(InvocationError, match="holds the TPU"):
            be._worker("p0")
    assert not started
    with ProcessBackend(_mini_castor, n_workers=1,
                        env={"JAX_PLATFORMS": "cpu"}) as be:
        (proc, _tq, _rq), _lock = be._worker("p0")
        assert proc.is_alive() and len(started) == 1


def test_process_backend_smoke_matches_fleet():
    """Real spawned containers (JSON wire, artifact ship-back): forecasts
    equal the fleet executor's, versions persisted with the invoker's
    lineage numbering, cold/warm telemetry recorded."""
    factory = functools.partial(build_steady_castor, "lr",
                                LinearForecaster, {}, n=2)
    c = factory()
    cf = factory()
    ex = ServerlessExecutor(c, backend=ProcessBackend(factory, n_workers=1),
                            speculative=False)
    try:
        for k in range(2):
            rb = ex.run(c.scheduler.poll(NOW + k * HOUR))
            assert rb and all(r.ok for r in rb), \
                [r.error for r in rb if not r.ok]
            ra = cf.tick(NOW + k * HOUR, executor="fleet")
            assert all(r.ok for r in ra)
        for i in range(2):
            fa = cf.predictions.history(f"s-Z_PRO_0_{i}")
            fb = c.predictions.history(f"s-Z_PRO_0_{i}")
            assert len(fa) == len(fb) == 2
            for x, y in zip(fa, fb):
                np.testing.assert_allclose(y.values, x.values,
                                           rtol=1e-6, atol=1e-8)
                assert y.model_version == x.model_version
            assert len(c.versions.history(f"s-Z_PRO_0_{i}")) == 1
        s = ex.stats()
        assert s["cold_starts"] == 1 and s["warm_starts"] >= 1
        assert s["queue_s_p95"] >= 0.0
    finally:
        ex.close()
