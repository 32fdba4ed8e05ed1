"""Chip smoke: the routed serving path, end to end, on one TPU chip at
published widths — the quickest proof that the system still starts there.

    python chip_smoke.py              # one chip: the gateway path
    python chip_smoke.py --chips 4    # four chips: the mesh retrieval paths

One chip boots `repro.serving.gateway.demo_gateway` over ``qwen3-4b`` and
``mamba2-370m`` at their published configs and a ``knn100-ivfpq`` router
(m = 192 PQ subspaces) fitted on 100,000 support rows of 768-d query
embeddings, then drives it the way a client does: ``/health``, then
streamed ``/v1/chat/completions`` at two ``@lam=`` values through the
micro-batcher, the query encoder, the fused route, `RouterService.execute`
and the engines' prefill and decode.  It then routes the same queries'
retrieval through the compiled Pallas kernels (``backend="pallas"``) and
holds them to the fused path.

``--chips 4`` runs only what exists across chips: the batch-sharded fused
route over a 4-device query mesh against the single-device route (bitwise),
and `sharded_ivfpq_topk` over 4 chips against the single-device top-k
(equal up to ties).

Everything runs in this one process (the gateway's HTTP and pump threads
included).  Without a TPU it exits non-zero before running anything.  The
labelled lines are bring-up observations, not benchmark numbers; the last
line of standard output is the JSON verdict.
"""
from __future__ import annotations

import argparse
import collections
import concurrent.futures
import http.client
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

POOL = ("qwen3-4b", "mamba2-370m")
ROUTER = "knn100-ivfpq@m=192"
SUPPORT_ROWS = 100_000
TRAIN_SHARE = 0.7            # `RoutingDataset.split`: the router fits on 70%
REQUESTS = 8
LAMS = (0.0, 100.0)          # quality-first, and cost-dominated
MAX_TOKENS = 8


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def backend() -> str:
    import jax
    return jax.default_backend()


def require_tpu() -> None:
    if backend() != "tpu":
        raise SystemExit(f"chip_smoke: JAX backend is {backend()!r}, not "
                         f"'tpu'; nothing was run")


def device() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def count_compiles() -> collections.Counter:
    """From here on, count the persistent compile cache's lookups, hits and
    writes, and the seconds XLA spends compiling or loading from the cache
    (`jax.monitoring` events)."""
    from jax import monitoring
    seen = collections.Counter()
    events = {"/jax/compilation_cache/compile_requests_use_cache": "lookups",
              "/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "writes"}

    def on_event(name, **_):
        if name in events:
            seen[events[name]] += 1

    def on_duration(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            seen["compile_or_load_s"] += secs

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    return seen


def say(label: str, **fields) -> None:
    print(f"[smoke] {label}: " + " ".join(f"{k}={v}" for k, v in
                                          fields.items()), flush=True)


def support_texts_for(rows: int) -> int:
    """Texts to embed so that the train split holds exactly ``rows``."""
    return math.ceil(rows / TRAIN_SHARE)


def queries(n: int = REQUESTS):
    from repro.launch.serve import TOPICS
    return [f"{TOPICS[i % len(TOPICS)]} request number {i}"
            for i in range(n)]


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def boot(*, published: bool = True, support_rows: int = SUPPORT_ROWS,
         router: str = ROUTER):
    """Build the gateway (unstarted) and report what it holds."""
    import jax
    from repro.serving.gateway import demo_gateway

    gw = demo_gateway(pool=POOL, router=router,
                      n_support=support_texts_for(support_rows),
                      published=published, engine_timeout_s=300.0)
    svc = gw.service
    for name, eng in svc.engines.items():
        leaves = jax.tree.leaves(eng.params)
        jax.block_until_ready(leaves)
        cfg = eng.cfg
        say(f"engine {name}", config=cfg.name, layers=cfg.total_blocks(),
            d_model=cfg.d_model, vocab=cfg.vocab_size, dtype=cfg.dtype,
            params=sum(x.size for x in leaves),
            param_bytes=sum(x.nbytes for x in leaves))
    rows = svc.router.support_size
    width = svc.router._X.shape[1]
    say("router", spec=svc.spec, support_rows=rows, width=width,
        m=svc.router._ivf.m, lists=svc.router._ivf.n_clusters,
        list_len=svc.router._ivf.list_size)
    say("boot seconds", **{k: round(v, 3) for k, v in gw.boot_s.items()})
    check(rows == support_rows, f"support rows {rows} != {support_rows}")
    return gw


def warm_up(gw) -> None:
    """Compile before serving, so no request waits on XLA: one decode
    dispatch per engine, and the encoder + fused route for every wave size
    the micro-batcher can close (1..max_batch)."""
    import numpy as np
    from repro.serving.engine import Request

    svc = gw.service
    for name, eng in svc.engines.items():
        t0 = time.perf_counter()
        req = Request(uid=-1, prompt_tokens=np.array([1], np.int32),
                      max_new_tokens=1)
        eng.run_until_drained([req])
        check(req.done and len(req.output_tokens) == 1,
              f"{name} warm-up request did not finish")
        say(f"first compile {name}", seconds=round(time.perf_counter() - t0,
                                                   3))
    texts = queries(gw.batcher.max_batch)
    t0 = time.perf_counter()
    for b in range(1, len(texts) + 1):
        svc.submit_texts(texts[:b], max_new_tokens=1)
    say("first compile route", wave_sizes=f"1..{len(texts)}",
        seconds=round(time.perf_counter() - t0, 3))


def _get(port: int, path: str):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        c.request("GET", path)
        r = c.getresponse()
        return r.status, json.loads(r.read())
    finally:
        c.close()


def _stream(port: int, model: str, text: str, max_tokens: int) -> dict:
    """One streamed completion, timed from the client's side."""
    body = json.dumps({"model": model, "stream": True,
                       "max_tokens": max_tokens,
                       "messages": [{"role": "user", "content": text}]})
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        t0 = time.perf_counter()
        c.request("POST", "/v1/chat/completions", body=body)
        r = c.getresponse()
        if r.status != 200:
            raise SmokeFailure(f"completion {r.status}: {r.read()!r}")
        served = r.getheader("X-Repro-Served-By")
        ttft, frames = None, []
        while True:
            line = r.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            frames.append(line[6:])
            if frames[-1] == b"[DONE]":
                break
            delta = json.loads(frames[-1])["choices"][0]["delta"]
            if ttft is None and delta.get("content", "").strip():
                ttft = time.perf_counter() - t0
        total = time.perf_counter() - t0
    finally:
        c.close()
    check(bool(frames) and frames[-1] == b"[DONE]",
          f"stream for {text!r} ended without [DONE]: {frames[-3:]!r}")
    chunks = [json.loads(f) for f in frames[:-1]]
    tokens = sum(bool(ch["choices"][0]["delta"].get("content", "").strip())
                 for ch in chunks)
    check(tokens == max_tokens,
          f"{text!r}: {tokens} tokens streamed, expected {max_tokens}")
    return {"served_by": served, "ttft_s": ttft, "total_s": total,
            "tokens": tokens}


def serve(gw, texts, lams, max_tokens: int = MAX_TOKENS) -> list:
    """Start the gateway, check /health, and stream one completion per
    text concurrently (so the micro-batcher coalesces them into waves)."""
    with gw:
        status, health = _get(gw.port, "/health")
        check(status == 200 and health.get("status") == "ok",
              f"/health {status}: {health}")
        say("health", status=health["status"], port=gw.port)
        models = [f"{gw.model_name}@lam={lam:g}" for lam in lams]
        with concurrent.futures.ThreadPoolExecutor(len(texts)) as pool:
            futs = [pool.submit(_stream, gw.port, models[i % len(models)],
                                text, max_tokens)
                    for i, text in enumerate(texts)]
            results = [f.result() for f in futs]
        status, _ = _get(gw.port, "/stats")
        check(status == 200, f"/stats {status}")
    check(not gw._pump_thread.is_alive() and not gw._http_thread.is_alive(),
          "gateway threads survived close()")
    for i, res in enumerate(results):
        say(f"request {i}", lam=f"{lams[i % len(lams)]:g}",
            served_by=res["served_by"], tokens=res["tokens"],
            ttft_s=round(res["ttft_s"], 4), total_s=round(res["total_s"], 4))
    mix = {}
    for res in results:
        mix[res["served_by"]] = mix.get(res["served_by"], 0) + 1
    say("routing mix", **mix, waves=gw.batcher.flushes)
    return results


def compare_topk(sc_a, ix_a, sc_b, ix_b, rows) -> dict:
    """Two top-k results over the support rows ``rows``: each row's scores,
    sorted, within rtol 1e-4 (atol 1e-5), and the share of equal ids
    position by position.  ``ids_equal_up_to_ties`` also counts as equal
    two ids that name identical support rows: an exact tie, which two
    correct searches may list in either order.  The synthetic support set
    holds many (its hash tokenizer maps the numbered texts onto ~40k
    distinct token sequences)."""
    import numpy as np
    sa, sb = np.sort(sc_a, axis=1), np.sort(sc_b, axis=1)
    np.testing.assert_allclose(sa, sb, rtol=1e-4, atol=1e-5)
    fin = np.isfinite(sa) & np.isfinite(sb)
    valid = (ix_a >= 0) & (ix_b >= 0)
    same_row = valid & np.all(rows[np.where(valid, ix_a, 0)]
                              == rows[np.where(valid, ix_b, 0)], axis=-1)
    return {"ids_equal": float(np.mean(ix_a == ix_b)),
            "ids_equal_up_to_ties": float(np.mean((ix_a == ix_b)
                                                  | same_row)),
            "max_abs_score_diff": float(np.max(np.abs(sa[fin] - sb[fin]),
                                               initial=0.0))}


def pallas_parity(svc, texts) -> None:
    """The same queries' retrieval through the compiled Pallas kernels
    against the fused path on the same index, held to the compiled-kernel
    tolerance of the IVF-PQ tests: scores within rtol 1e-4 and at least 99%
    of ids equal (exact ties between identical support rows counted as
    equal)."""
    import numpy as np
    from repro.serving import encoder

    emb = encoder.embed_texts(list(texts))
    router = svc.router
    t0 = time.perf_counter()
    sc_p, ix_p = router._neighbors(emb, backend="pallas")
    t_pallas = time.perf_counter() - t0
    sc_f, ix_f = router._neighbors(emb, backend="fused")
    cmp = compare_topk(*(np.asarray(a) for a in (sc_p, ix_p, sc_f, ix_f)),
                       router._X)
    say("pallas vs fused", queries=len(texts), k=ix_p.shape[1], **cmp,
        first_call_s=round(t_pallas, 3))
    check(cmp["ids_equal_up_to_ties"] >= 0.99,
          f"pallas vs fused ids agree on {cmp['ids_equal_up_to_ties']:.4f}"
          f" < 0.99")


def run_one_chip(*, published: bool = True,
                 support_rows: int = SUPPORT_ROWS) -> None:
    require_tpu()
    gw = boot(published=published, support_rows=support_rows)
    warm_up(gw)
    texts = queries()
    serve(gw, texts, LAMS)
    pallas_parity(gw.service, texts)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def run_four_chips(*, support_rows: int = SUPPORT_ROWS,
                   router: str = ROUTER) -> None:
    require_tpu()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.sharded_knn import sharded_ivfpq_topk
    from repro.kernels.knn_ivf.ops import ivfpq_topk
    from repro.launch.serve import build_support
    from repro.serving import encoder
    from repro.serving.router_service import RouterService

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    mesh = jax.make_mesh((4,), ("q",), devices=devs[:4],
                         axis_types=(jax.sharding.AxisType.Auto,))
    t0 = time.perf_counter()
    ds = build_support(list(POOL), n=support_texts_for(support_rows))
    # routing only: no engine runs in this phase
    svc = RouterService(router, {m: None for m in POOL}, ds=ds)
    say("router", spec=svc.spec, support_rows=svc.router.support_size,
        width=ds.dim, boot_s=round(time.perf_counter() - t0, 3))
    texts = queries(2 * REQUESTS)
    emb = encoder.embed_texts(texts)
    lam = np.asarray([LAMS[i % len(LAMS)] for i in range(len(texts))],
                     np.float32)

    one = svc.route_fused(emb, lam)
    sharded = svc.route_fused(emb, lam, qmesh=mesh)
    for name, a, b in zip(("choice", "s_hat", "c_hat", "agreement"),
                          one[:4], sharded[:4]):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              f"batch-sharded route {name} differs from one device")
    say("batch-sharded route", devices=4, queries=len(texts),
        bitwise_equal=True)

    r = svc.router
    q = jnp.asarray(emb / np.linalg.norm(emb, axis=1, keepdims=True))
    sc1, ix1 = ivfpq_topk(q, r._ivf, r.k, nprobe=r.nprobe, rerank=r.rerank,
                          backend="fused")
    sc4, ix4 = sharded_ivfpq_topk(q, r._ivf, r.k, mesh, nprobe=r.nprobe,
                                  rerank=r.rerank)
    cmp = compare_topk(np.asarray(sc4), np.asarray(ix4), np.asarray(sc1),
                       np.asarray(ix1), r._X)
    say("row-sharded ivfpq top-k", devices=4, k=ix4.shape[1], **cmp)
    check(cmp["ids_equal_up_to_ties"] >= 0.99,
          f"row-sharded vs one-device ids agree on "
          f"{cmp['ids_equal_up_to_ties']:.4f} < 0.99")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh retrieval paths")
    args = ap.parse_args(argv)
    require_tpu()
    from repro.compile_cache import enable_compile_cache
    compiles = count_compiles()
    say("compile cache", dir=enable_compile_cache())
    if args.chips == 4:
        run_four_chips()
    else:
        run_one_chip()
    say("compiles", **{k: round(compiles[k], 3) for k in
                       ("lookups", "hits", "writes", "compile_or_load_s")})
    print(json.dumps({"ok": True, "device": device()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
