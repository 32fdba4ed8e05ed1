"""A whole run on the CPU, the chip check skipped, with the timed path
broken underneath: ``correct`` has to come out false, by the number that
the fault reaches."""
import io
import json
import contextlib
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import run
from harness import boot

DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops_per_s": 1e12}


def bench_run(monkeypatch, plant=None, workload="tiny.chat"):
    if plant is not None:
        build = boot.build_engines

        def planted(config):
            engines = build(config)
            plant(engines)
            return engines
        monkeypatch.setattr(boot, "build_engines", planted)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(2 ** 31 + 9),
                       "--seconds", "3", "--trace", "0",
                       "--bench-json", str(DATA / "BENCHMARK.json"),
                       "--data-dir", str(DATA)], chip=False, peaks=PEAKS)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def state_unchanged(engines):
    """Each decode step returns the caches it was given."""
    for eng in engines.values():
        step = eng._decode
        eng._decode = (lambda step: lambda p, c, t, q: (step(p, c, t, q)[0],
                                                        c))(step)


def token_altered(engines):
    """Every token is the one after the step's greedy choice."""
    for eng in engines.values():
        step = eng._decode
        eng._decode = (lambda step: lambda p, c, t, q: (
            jnp.roll(step(p, c, t, q)[0], 1, axis=-1),
            step(p, c, t, q)[1]))(step)


def answer_altered(monkeypatch):
    from repro.serving.router_service import RouterService
    fused = RouterService.route_fused

    def wrong(self, *a, **kw):
        choice, *rest = fused(self, *a, **kw)
        return ((np.asarray(choice) + 1) % len(self.model_names), *rest)
    monkeypatch.setattr(RouterService, "route_fused", wrong)


def over(result, prefix):
    return [k for k, c in result["checks"].items()
            if k.startswith(prefix) and c["value"] > c["limit"]]


def test_sound_run_reports_every_check(monkeypatch):
    res = bench_run(monkeypatch)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["route_regret_mean"]["value"] == 0.0
    assert {"ttft_p50_ms", "ttft_p90_ms", "setup_s"} <= set(res["metrics"])
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [state_unchanged, token_altered],
                         ids=lambda f: f.__name__)
def test_engine_faults_fail(monkeypatch, fault):
    res = bench_run(monkeypatch, fault)
    assert res["correct"] is False
    assert over(res, "logit_gap.")


def test_altered_routing_answer_fails(monkeypatch):
    answer_altered(monkeypatch)
    res = bench_run(monkeypatch)
    assert res["correct"] is False
    assert over(res, "route_regret_mean")


def embedding_altered(monkeypatch):
    """Each embedding of a wave is the next request's."""
    from repro.serving import encoder
    embed = encoder.embed_texts
    monkeypatch.setattr(encoder, "embed_texts",
                        lambda texts: np.roll(embed(texts), 1, axis=0))


def half_wave_routed(monkeypatch):
    """Only the first half of each wave is routed; its answers are given
    again for the second half."""
    from repro.serving.router_service import RouterService
    fused = RouterService.route_fused

    def half(self, emb, lam=None, **kw):
        n = len(emb)
        out = fused(self, emb[:max(1, n // 2)], lam[:max(1, n // 2)], **kw)
        return tuple(np.resize(o, (n,) + np.shape(o)[1:])
                     if o is not None else None for o in out)
    monkeypatch.setattr(RouterService, "route_fused", half)


def test_sound_route_run_is_correct(monkeypatch):
    res = bench_run(monkeypatch, workload="tiny.route")
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"routes_per_s", "setup_s"} == set(res["metrics"])
    assert set(res["checks"]) >= {"route_regret_mean", "score_gap_mean",
                                  "embed_gap"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [answer_altered, embedding_altered,
                                   half_wave_routed],
                         ids=lambda f: f.__name__)
def test_route_faults_fail(monkeypatch, fault):
    fault(monkeypatch)
    res = bench_run(monkeypatch, workload="tiny.route")
    assert res["correct"] is False
    assert over(res, "route_regret_mean") or over(res, "score_gap_mean") \
        or over(res, "embed_gap")
