"""Routed-serving driver: build a pool of engines (reduced smoke configs,
or the published ones with ``--published``), fit a spec-addressed router on
a synthetic routing benchmark projected into the query encoder's embedding
space, then serve a stream of text requests at a per-request cost/quality
lambda.

  PYTHONPATH=src python -m repro.launch.serve --pool qwen3-4b mamba2-370m \
      h2o-danube-1.8b --requests 12 --router knn10 --save-artifact /tmp/r

With ``--save-artifact`` the fitted router is persisted (npz + manifest) and
the service is re-booted from the artifact before serving — the deployment
path where the server never sees the training data.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.configs import get_config, reduced
from repro.core.dataset import RoutingDataset
from repro.serving import encoder
from repro.serving.engine import ServingEngine
from repro.serving.pipeline import RoutingPipeline
from repro.serving.router_service import RouterService

TOPICS = ["python programming", "world history", "algebra proofs",
          "poetry writing", "biology facts"]


def pool_config(name: str, published: bool = False):
    """A pool model's config: its published widths, or the `reduced()`
    smoke variant of the same architecture."""
    cfg = get_config(name)
    return cfg if published else reduced(cfg)


def build_support(pool, n=300, seed=0):
    """Synthetic routing support set in the ENCODER's embedding space: each
    pool model is strong on some topics (smooth in embedding space)."""
    rng = np.random.default_rng(seed)
    texts = [f"{TOPICS[i % len(TOPICS)]} question {i}" for i in range(n)]
    emb = encoder.embed_texts(texts)
    M = len(pool)
    centers = encoder.embed_texts(TOPICS)
    affinity = rng.uniform(0.2, 1.0, (len(TOPICS), M))
    topic = np.array([i % len(TOPICS) for i in range(n)])
    scores = np.clip(affinity[topic] + rng.normal(0, 0.05, (n, M)), 0, 1)
    costs = np.tile(rng.uniform(0.001, 0.01, M), (n, 1)).astype(np.float32)
    return RoutingDataset("serve-support", emb, scores.astype(np.float32),
                          costs, list(pool))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--pool", nargs="+",
                    default=["qwen3-4b", "mamba2-370m", "h2o-danube-1.8b"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--lam", type=float, default=1.0)
    ap.add_argument("--router", default="knn10",
                    help="router spec string, e.g. knn10, knn100-ivf@lam=0.5")
    ap.add_argument("--save-artifact", default=None,
                    help="persist the fitted router here and re-boot the "
                         "service from the artifact before serving")
    ap.add_argument("--published", action="store_true",
                    help="build the engines at their published widths "
                         "instead of the reduced smoke configs")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    engines = {}
    for i, name in enumerate(args.pool):
        cfg = pool_config(name, args.published)
        engines[name] = ServingEngine(cfg, max_slots=2, cache_len=64, seed=i)
        print(f"[pool] {name}: {cfg.name} {cfg.total_blocks()} blocks, "
              f"d_model {cfg.d_model}")

    ds = build_support(args.pool)
    pipe = RoutingPipeline(args.router).fit(ds)
    if args.save_artifact:
        path = pipe.save(args.save_artifact)
        print(f"[artifact] saved {pipe.spec} -> {path}")
        svc = RouterService.from_artifact(path, engines,
                                          fallback_model=args.pool[0])
    else:
        svc = pipe.serve(engines, fallback_model=args.pool[0])

    reqs = [f"{TOPICS[i % len(TOPICS)]} request number {i}"
            for i in range(args.requests)]
    # per-request lambda: even requests at the CLI trade-off, odd requests
    # quality-first (lam=0) — one batch, two operating points
    lams = np.where(np.arange(len(reqs)) % 2 == 0, args.lam, 0.0)
    results = svc.serve_texts(reqs, max_new_tokens=args.max_new,
                              lam=lams.astype(np.float32))
    for r in results:
        print(f"  req {r.uid} -> {r.model:24s} s_hat={r.predicted_score:.2f} "
              f"lam={r.lam:.2f} conf={r.confidence:.2f} "
              f"tokens={r.request.output_tokens}")
    counts = {}
    for r in results:
        counts[r.model] = counts.get(r.model, 0) + 1
    print("[routing mix]", counts)
    return results


if __name__ == "__main__":
    main()
