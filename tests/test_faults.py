"""Fault-tolerant serving: circuit breakers, availability-masked fused
selection, deadline-driven degraded retrieval, bounded-queue shedding,
failure isolation + deterministic reroute in execute(), and chaos
interleavings under the deadlock watchdog."""
import time

import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.dataset import RoutingDataset
from repro.core.routers.knn import KNNRouter
from repro.serving import encoder
from repro.serving.engine import (IncompleteDrainError, Request,
                                  ServingEngine)
from repro.serving.faults import (CLOSED, DEFAULT_LEVELS, HALF_OPEN, OPEN,
                                  DegradationLadder, EngineDeadlineExceeded,
                                  EngineHealth, ExecutionReport,
                                  FaultInjector, InjectedFault, Overloaded)
from repro.serving.router_service import RouterService
from repro.serving.scheduler import MicroBatcher


def _routing_ds(names, n=60, seed=0):
    texts = [f"topic {i % 3} example {i}" for i in range(n)]
    emb = encoder.embed_texts(texts)
    rng = np.random.default_rng(seed)
    return RoutingDataset(
        "mini", emb,
        rng.uniform(0.2, 1.0, (n, len(names))).astype(np.float32),
        rng.uniform(0.001, 0.01, (n, len(names))).astype(np.float32),
        list(names))


def _engines(names, max_slots=2):
    return {n: ServingEngine(reduced(get_config("qwen3-4b")),
                             max_slots=max_slots, cache_len=48, seed=i)
            for i, n in enumerate(names)}


def _warm(engines):
    """Run one tiny wave through each engine so its per-instance jit
    compiles up front — deadline tests must measure the hang, not the
    first-wave compile."""
    for eng in engines.values():
        req = Request(uid=-1, prompt_tokens=np.arange(4, dtype=np.int64)
                      % eng.cfg.vocab_size, max_new_tokens=1)
        eng.run_until_drained([req])


# ---------------------------------------------------------------------------
# circuit breaker state machine
# ---------------------------------------------------------------------------

def test_breaker_transitions_under_injected_failures():
    t = [0.0]
    h = EngineHealth("m", failure_threshold=2, base_backoff_s=1.0,
                     clock=lambda: t[0])
    assert h.state == CLOSED and h.available()
    h.record_failure(RuntimeError("one"))
    assert h.state == CLOSED                       # below threshold
    h.record_failure(RuntimeError("two"))
    assert h.state == OPEN and not h.available()
    assert h.retry_after_s() == pytest.approx(1.0)
    # backoff not yet elapsed: still gated
    t[0] = 0.5
    assert not h.available()
    # backoff elapsed: the next wave is the probe
    t[0] = 1.0
    assert h.available() and h.state == HALF_OPEN
    # failed probe re-opens with DOUBLED backoff
    h.record_failure(RuntimeError("probe failed"))
    assert h.state == OPEN and h.backoff_s == pytest.approx(2.0)
    t[0] = 2.0
    assert not h.available()                       # 2s backoff from t=1.0
    t[0] = 3.0
    assert h.available() and h.state == HALF_OPEN
    # successful probe re-closes AND resets the backoff ladder
    h.record_success()
    assert h.state == CLOSED and h.backoff_s == pytest.approx(1.0)
    assert h.consecutive_failures == 0
    st = h.stats()
    assert st["state"] == "closed" and st["opens"] == 2
    assert st["failures"] == 3 and st["successes"] == 1
    assert st["probes"] == 2
    assert "probe failed" in st["last_error"]


def test_breaker_counts_timeouts_and_caps_backoff():
    t = [0.0]
    h = EngineHealth("m", failure_threshold=1, base_backoff_s=1.0,
                     max_backoff_s=4.0, clock=lambda: t[0])
    h.record_failure(EngineDeadlineExceeded("m", 0.5))
    assert h.state == OPEN and h.stats()["timeouts"] == 1
    for _ in range(5):                             # repeated failed probes
        t[0] += 100.0
        assert h.available()
        h.record_failure(RuntimeError("still down"))
    assert h.backoff_s == pytest.approx(4.0)       # capped, not 32
    with pytest.raises(ValueError, match="failure_threshold"):
        EngineHealth("m", failure_threshold=0)


# ---------------------------------------------------------------------------
# availability-masked fused selection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("index", ["exact", "ivf", "ivfpq"])
def test_masked_selection_parity_and_exclusion(index):
    names = ["a", "b", "c"]
    ds = _routing_ds(names, n=60)
    kw = {} if index == "exact" else {"n_clusters": 4}
    r = KNNRouter(k=5, index=index, **kw).fit(ds)
    emb = ds.embeddings[:8]
    lam = np.full(8, 0.5, np.float32)
    base = r.serve_fused(emb, lam)
    # all-ones mask is BITWISE identical to no mask (the parity guarantee
    # the sanitizer/parity suites rely on)
    ones = r.serve_fused(emb, lam, avail=np.ones(3, bool))
    for got, want in zip(ones, base):
        np.testing.assert_array_equal(got, want)
    choice, s_hat, c_hat = base[0], base[1], base[2]
    # mask out the most-picked model: it must vanish from the choices and
    # the selection must equal the host-side masked argmax exactly
    down = int(np.bincount(choice, minlength=3).argmax())
    mask = np.ones(3, bool)
    mask[down] = False
    mchoice, ms, mc, _, _ = r.serve_fused(emb, lam, avail=mask)
    assert down not in set(mchoice.tolist())
    util = ms - lam[:, None] * mc
    util[:, down] = -np.inf
    np.testing.assert_array_equal(mchoice, np.argmax(util, axis=1))
    # utilities themselves stay UNmasked — reports show true estimates
    np.testing.assert_array_equal(ms, s_hat)
    np.testing.assert_array_equal(mc, c_hat)
    with pytest.raises(ValueError, match="excludes every model"):
        r.serve_fused(emb, lam, avail=np.zeros(3, bool))
    with pytest.raises(ValueError, match="shape"):
        r.serve_fused(emb, lam, avail=np.ones(4, bool))


def test_route_fused_masks_open_breakers():
    """An open breaker re-routes INSIDE the fused dispatch: the down model
    never appears in choices, and recovery restores the original routing."""
    names = ["cheap-weak", "pricey-strong"]
    ds = _routing_ds(names)
    ds.scores[:, 0], ds.scores[:, 1] = 0.2, 0.9     # model 1 always wins
    ds.costs[:, 0], ds.costs[:, 1] = 0.001, 0.01
    t = [0.0]
    svc = RouterService(KNNRouter(k=5).fit(ds), {names[0]: None,
                                                 names[1]: None},
                        lam=0.0,
                        breaker={"failure_threshold": 1,
                                 "base_backoff_s": 10.0,
                                 "clock": lambda: t[0]})
    emb = ds.embeddings[:4]
    assert svc.route_embeddings(emb).tolist() == [1, 1, 1, 1]
    svc.health[names[1]].record_failure(RuntimeError("down"))
    assert svc.availability_mask().tolist() == [True, False]
    assert svc.route_embeddings(emb).tolist() == [0, 0, 0, 0]
    # breaker recovery: probe window admits, success re-closes
    t[0] = 10.0
    svc.health[names[1]].available()
    svc.health[names[1]].record_success()
    assert svc.availability_mask() is None          # all-up fast path
    assert svc.route_embeddings(emb).tolist() == [1, 1, 1, 1]


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------

def test_ladder_level_selection_and_clamping():
    lad = DegradationLadder()
    assert lad.level_for(0, 64) == 0
    assert lad.level_for(64, 64, headroom=1.0) == 0      # one wave: fine
    assert lad.level_for(200, 64, headroom=1.0) == 1     # > 2 waves deep
    assert lad.level_for(0, 64, headroom=0.3) == 1       # deadline pressure
    assert lad.level_for(0, 64, headroom=0.2) == 2
    assert lad.level_for(600, 64, headroom=0.05) == 3
    assert lad[99].level == 3 and lad[-5].level == 0     # clamped lookup
    assert DEFAULT_LEVELS[3].skip_delta and DEFAULT_LEVELS[3].rerank == 0


def test_degraded_context_restores_and_floors_recall():
    names = ["a", "b"]
    ds = _routing_ds(names, n=200)
    r = KNNRouter(k=10, index="ivf", n_clusters=8, online=True).fit(ds)
    # grow a delta tier so base-only (skip_delta) actually gives rows up
    extra = _routing_ds(names, n=20, seed=7)
    r.partial_fit(extra.embeddings, extra.scores, extra.costs,
                  recluster=False)
    assert r._ivf.delta_rows == 20
    q = ds.embeddings[:16]
    exact = KNNRouter(k=10, index="exact").fit(ds)
    exact.partial_fit(extra.embeddings, extra.scores, extra.costs)
    _, gold = exact._neighbors(q)
    saved = (r.nprobe, r.rerank, r._skip_delta)
    recalls = []
    for level in DEFAULT_LEVELS:
        with r.degraded(level):
            if level.level:
                assert r.nprobe <= saved[0]
            _, idx = r._neighbors(q)
        hits = sum(len(set(map(int, idx[i])) & set(map(int, gold[i])))
                   for i in range(len(q)))
        recalls.append(hits / gold.size)
    # overrides restored exactly after every wave
    assert (r.nprobe, r.rerank, r._skip_delta) == saved
    # full fidelity is near-exact; every rung keeps a usable floor
    assert recalls[0] >= 0.95
    assert all(rc >= 0.3 for rc in recalls)
    # base-only serves from the compacted base: appended rows absent
    with r.degraded(DEFAULT_LEVELS[3]):
        _, idx3 = r._neighbors(extra.embeddings[:4])
    assert not (set(map(int, idx3.ravel())) & set(range(200, 220)))


def test_degraded_wave_annotation_through_batcher():
    """A pressured queue serves degraded waves and annotates every result
    with the level; an idle queue serves at full fidelity."""
    names = ["a", "b"]
    ds = _routing_ds(names)
    svc = RouterService(KNNRouter(k=5, index="ivf", n_clusters=4).fit(ds),
                        _engines(names), lam=1.0)
    clock = [0.0]
    mb = MicroBatcher(svc, max_batch=4, deadline_s=1.0,
                      ladder=svc.ladder, clock=lambda: clock[0])
    for i in range(4):
        mb.submit(f"calm {i}")
    calm = mb.flush()
    assert all(res.degradation == 0 for res in calm)
    for i in range(4):
        mb.submit(f"rushed {i}")
    clock[0] = 0.95                                  # 5% deadline headroom
    rushed = mb.flush()
    assert mb.last_degradation == 3 and mb.degraded_waves == 1
    assert all(res.degradation == 3 for res in rushed)


# ---------------------------------------------------------------------------
# bounded-queue admission control
# ---------------------------------------------------------------------------

class _StubService:
    default_lam = 0.0

    def submit_texts(self, texts, max_new_tokens=8, lam=None):
        return [{"text": t} for t in texts]


def test_microbatcher_sheds_then_recovers():
    mb = MicroBatcher(_StubService(), max_batch=2, max_pending=3)
    tickets = [mb.submit(f"q{i}") for i in range(3)]
    with pytest.raises(Overloaded) as ei:
        mb.submit("q3")
    assert ei.value.pending == 3 and ei.value.retry_after_s > 0
    assert mb.shed == 1
    mb.flush()                                       # drains 2 of 3
    t3 = mb.submit("q3")                             # recovered
    mb.flush()
    mb.flush()
    for t in tickets + [t3]:
        assert mb.pop_result(t) is not None          # nothing was dropped
    with pytest.raises(ValueError, match="max_pending"):
        MicroBatcher(_StubService(), max_pending=0)


# ---------------------------------------------------------------------------
# incomplete drain is an error, not a truncation
# ---------------------------------------------------------------------------

def test_run_until_drained_raises_and_marks_survivors():
    eng = ServingEngine(reduced(get_config("qwen3-4b")), max_slots=1,
                        cache_len=48, seed=0)
    reqs = [Request(uid=i, prompt_tokens=np.array([3 + i]),
                    max_new_tokens=8) for i in range(2)]
    with pytest.raises(IncompleteDrainError) as ei:
        eng.run_until_drained(list(reqs), max_steps=2)
    err = ei.value
    assert err.steps == 2 and len(err.survivors) == 2
    assert {r.uid for r in err.survivors} == {0, 1}
    assert all(r.error == "incomplete_drain" for r in reqs)
    assert not any(r.done for r in reqs)
    # slots reclaimed: the engine serves the next wave normally
    assert all(s is None for s in eng.slot_req)
    ok = Request(uid=2, prompt_tokens=np.array([9]), max_new_tokens=2)
    eng.run_until_drained([ok])
    assert ok.done


# ---------------------------------------------------------------------------
# execute(): isolation, deterministic reroute, deadlines, typed failure
# ---------------------------------------------------------------------------

def _biased_service(names, engines, **kw):
    """model 1 strictly better and pricier, so lam=0 routes all to it."""
    ds = _routing_ds(names)
    ds.scores[:, 0], ds.scores[:, 1] = 0.2, 0.9
    ds.costs[:, 0], ds.costs[:, 1] = 0.001, 0.01
    return RouterService(KNNRouter(k=5).fit(ds), engines, lam=0.0, **kw)


def test_execute_isolates_failure_and_reroutes_next_best():
    names = ["backup", "primary"]
    engines = _engines(names)
    boom = FaultInjector(engines[names[1]], mode="raise")
    engines[names[1]] = boom
    svc = _biased_service(names, engines,
                          breaker={"failure_threshold": 1,
                                   "base_backoff_s": 60.0})
    results = svc.submit_texts([f"q {i}" for i in range(3)],
                               max_new_tokens=2)
    assert all(r.model == names[1] for r in results)
    report = svc.execute(results)
    assert isinstance(report, ExecutionReport)
    # the failed engine is isolated and reported; the wave is NOT lost
    assert list(report.errors) == [names[1]]
    assert report.errors[names[1]][0]["error"] == "InjectedFault"
    # deterministic next-best reroute: every request served by the backup
    assert sorted(report.rerouted) == [(r.uid, names[1], names[0])
                                       for r in sorted(results,
                                                       key=lambda r: r.uid)]
    assert all(r.model == names[0] for r in results)
    assert all(r.rerouted_from == [names[1]] for r in results)
    assert all(r.request.done for r in results)
    # predictions re-attributed to the engine that actually served
    mi = svc.model_names.index(names[0])
    assert all(r.predicted_score == pytest.approx(float(r.s_row[mi]))
               for r in results)
    assert report[names[0]] > 0 and names[1] not in report
    assert not report.ok and not report.failed
    assert len(svc.log) == 3
    # the breaker opened (threshold 1) — the NEXT batch routes around the
    # outage inside the fused dispatch, and execute skips the engine
    assert svc.health[names[1]].state == OPEN
    more = svc.submit_texts(["again"], max_new_tokens=2)
    assert more[0].model == names[0]
    rep2 = svc.execute(more)
    assert rep2.ok and more[0].request.done


def test_execute_total_outage_is_typed_not_silent():
    names = ["backup", "primary"]
    engines = {n: FaultInjector(e, mode="raise")
               for n, e in _engines(names).items()}
    svc = _biased_service(names, engines)
    results = svc.submit_texts(["doomed"], max_new_tokens=2)
    report = svc.execute(results)
    # every candidate tried, then a typed terminal failure — never a drop
    assert set(report.failed) == {r.uid for r in results}
    assert "InjectedFault" in report.failed[results[0].uid]
    assert results[0].request.error == "InjectedFault"
    assert not results[0].request.done
    assert len(report.errors) == 2
    assert len(svc.log) == 1                        # the log survives


def test_execute_hung_engine_hits_deadline_and_reroutes():
    names = ["backup", "primary"]
    engines = _engines(names)
    _warm(engines)
    hang = FaultInjector(engines[names[1]], mode="hang")
    engines[names[1]] = hang
    svc = _biased_service(names, engines,
                          engine_timeout_s=0.25,
                          breaker={"failure_threshold": 1,
                                   "base_backoff_s": 60.0})
    results = svc.submit_texts(["stuck?"], max_new_tokens=2)
    t0 = time.monotonic()
    report = svc.execute(results)
    assert time.monotonic() - t0 < 10.0             # did not block forever
    assert report.errors[names[1]][0]["error"] == "EngineDeadlineExceeded"
    assert svc.health[names[1]].stats()["timeouts"] == 1
    assert results[0].model == names[0] and results[0].request.done
    hang.heal()                                     # release the worker


def test_execute_skips_open_breaker_without_touching_engine():
    names = ["backup", "primary"]
    engines = _engines(names)
    spy = FaultInjector(engines[names[1]])          # healthy, counts waves
    engines[names[1]] = spy
    svc = _biased_service(names, engines,
                          breaker={"failure_threshold": 1,
                                   "base_backoff_s": 60.0})
    results = svc.submit_texts(["gated"], max_new_tokens=2)
    assert results[0].model == names[1]
    svc.health[names[1]].record_failure(RuntimeError("opened by hand"))
    report = svc.execute(results)
    assert spy.waves == 0                           # engine never dispatched
    assert report.skipped == {names[1]: 1}
    assert results[0].model == names[0] and results[0].request.done


# ---------------------------------------------------------------------------
# chaos: injected raise-then-hang during append + recluster + close
# ---------------------------------------------------------------------------

def test_chaos_outage_recovery_no_wave_lost(watchdog):
    """One of three engines fault-injected (raise, then hang) while feedback
    appends trigger background recluster and close() runs concurrently:
    every submitted ticket resolves to a rerouted completed result or a
    typed shed/error, and the breaker re-closes after recovery."""
    names = ["m0", "m1", "m2"]
    engines = _engines(names)
    _warm(engines)
    chaos = FaultInjector(engines[names[1]])
    engines[names[1]] = chaos
    ds = _routing_ds(names, n=80)
    ds.scores[:] = 0.2
    ds.scores[:, 1] = 0.9                            # lam=0 routes all to m1
    router = KNNRouter(k=5, index="ivf", n_clusters=4, online=True,
                       delta_cap=30).fit(ds)
    svc = RouterService(router, engines, lam=0.0,
                        engine_timeout_s=0.5,
                        breaker={"failure_threshold": 1,
                                 "base_backoff_s": 0.05})
    mb = MicroBatcher(svc, max_batch=4, max_pending=64)
    tickets = []
    shed = []
    reports = []

    def serve_worker():
        # wave 0 healthy -> wave 1 raise -> wave 2 hang -> waves 3-4 healed
        for wave, mode in enumerate([None, "raise", "hang", None, None]):
            chaos.set_mode(mode)
            # let any open breaker's backoff (0.05s, doubled once to 0.1s)
            # elapse, so each wave's routing sees the probe window
            time.sleep(0.12)
            for i in range(4):
                try:
                    tickets.append(mb.submit(f"wave {wave} req {i}"))
                except Overloaded as exc:
                    shed.append(exc)
            batch = mb.flush()
            reports.append(svc.execute(batch))
        mb.close()

    def observe_worker():
        feed = _routing_ds(names, n=10, seed=3)
        # the feedback agrees that m1 is best, so whenever it lands, waves
        # 1-2 still route to m1 and meet its faults
        feed.scores[:] = 0.2
        feed.scores[:, 1] = 0.9
        for _ in range(4):
            svc.observe(feed.embeddings, feed.scores, feed.costs,
                        recluster="background")
            time.sleep(0.01)

    def close_worker():
        for _ in range(3):
            svc.close()
            time.sleep(0.02)

    watchdog([serve_worker, observe_worker, close_worker], timeout=240)
    chaos.heal()

    # no wave lost: every ticket resolves to a completed (possibly
    # rerouted) result or a typed terminal error — zero silent drops
    assert len(tickets) == 20 and not shed
    resolved = [mb.pop_result(t) for t in tickets]
    assert all(res is not None for res in resolved)
    for res in resolved:
        assert res.request.done or res.request.error, res.uid
    done = [res for res in resolved if res.request.done]
    failed = [res for res in resolved if not res.request.done]
    assert len(done) >= 16                           # only wave 2 may fail
    all_failed = {uid for rep in reports for uid in rep.failed}
    assert {res.uid for res in failed} <= all_failed
    # faults really fired and were rerouted around
    assert chaos.injected["raise"] >= 1 and chaos.injected["hang"] >= 1
    rerouted = [t for rep in reports for t in rep.rerouted]
    assert any(frm == names[1] for _, frm, _ in rerouted)
    # recovery: the breaker re-closed after the healed probe wave
    assert svc.health[names[1]].state == CLOSED
    assert svc.stats()["engines"][names[1]]["opens"] >= 1
    # the feedback loop kept running underneath the outage
    assert svc.observed == 40
