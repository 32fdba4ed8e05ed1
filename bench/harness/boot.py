"""Building the system under test from the program's own parts:
`ServingEngine` per pool model (weights made here, on the device), the
router through `RouterService.from_artifact`, then `Gateway`.  Warm-up
compiles every shape the cell's traffic uses.
"""
from __future__ import annotations

import collections
import importlib
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Dict

from .spec import BENCH_DIR, ROOT

#: the router is fitted on 70% of the support texts (`RoutingDataset.split`)
TRAIN_SHARE = 0.7


def program_path() -> None:
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def arch_module(name: str):
    if str(BENCH_DIR) not in sys.path:
        sys.path.insert(0, str(BENCH_DIR))
    return importlib.import_module(f"arch.{name}")


def make_weights(engine: dict):
    """One jitted call, on the device, from the engine's seed, in bf16."""
    import jax
    import jax.numpy as jnp
    arch = arch_module(engine["arch"])
    hf = engine["config"]
    init = jax.jit(lambda key: arch.init(key, hf, jnp.bfloat16))
    params = init(jax.random.PRNGKey(int(engine["seed"])))
    jax.block_until_ready(params)
    return params


def check_layout(params, cfg) -> None:
    """The weights made here must have the layout, shapes and dtypes of the
    program's own initialisation for the same config."""
    import jax
    from repro.models import model as M
    want = jax.eval_shape(M.init_params, jax.random.PRNGKey(0), cfg)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError(f"weights for {cfg.name} do not match the "
                           f"program's parameter layout")


def build_engines(config: dict) -> Dict[str, object]:
    program_path()
    from repro.configs.base import ModelConfig
    from repro.serving.engine import ServingEngine
    eng_kw = config["engine"]
    engines = {}
    for e in config["pool"]:
        cfg = arch_module(e["arch"]).program_config(e["config"], ModelConfig,
                                                    e["name"])
        params = make_weights(e)
        check_layout(params, cfg)
        engines[e["name"]] = ServingEngine(
            cfg, params=params, max_slots=int(eng_kw["max_slots"]),
            cache_len=int(eng_kw["cache_len"]))
    return engines


def support_texts(config: dict) -> int:
    """Texts to embed so that the router's 70% share holds ``rows``."""
    return math.ceil(config["router"]["support_rows"] / TRAIN_SHARE)


def artifact_dir(config: dict, cache_dir: Path) -> Path:
    return cache_dir / "router" / config["name"]


def router_service(config: dict, engines, cache_dir: Path, timings: dict):
    """`RouterService.from_artifact` over the cached router artifact.  The
    first run of a configuration embeds the support set, fits the router
    and saves the artifact there (the cell's compiling run)."""
    program_path()
    from repro.core.routers import save_router
    from repro.launch.serve import build_support
    from repro.serving.router_service import RouterService
    r = config["router"]
    path = artifact_dir(config, cache_dir)
    svc_kw = ({"engine_timeout_s": config["gateway"]["engine_timeout_s"]}
              if "gateway" in config else {})
    if not (path / "manifest.json").is_file():
        t0 = time.perf_counter()
        ds = build_support([e["name"] for e in config["pool"]],
                           n=support_texts(config), seed=int(r["seed"]))
        timings["support_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        svc = RouterService(r["spec"], engines, ds=ds, seed=int(r["seed"]))
        if svc.router.support_size != r["support_rows"]:
            raise RuntimeError(f"router holds {svc.router.support_size} "
                               f"rows, config says {r['support_rows']}")
        timings["fit_s"] = time.perf_counter() - t0
        tmp = path.with_name(path.name + ".tmp")
        shutil.rmtree(tmp, ignore_errors=True)
        save_router(svc.router, tmp)
        shutil.rmtree(path, ignore_errors=True)
        tmp.rename(path)
        svc.close()
    t0 = time.perf_counter()
    svc = RouterService.from_artifact(path, engines, **svc_kw)
    timings["router_load_s"] = time.perf_counter() - t0
    return svc


def gateway(config: dict, svc):
    from repro.serving.gateway import Gateway
    g = config["gateway"]
    return Gateway(svc, max_batch=int(g["max_batch"]),
                   close_timeout_s=float(g["close_timeout_s"]),
                   max_pending=int(g["max_pending"]),
                   max_new_tokens_cap=int(g["max_new_tokens_cap"]),
                   request_timeout_s=float(g["request_timeout_s"]))


def warm_up(gw, engines) -> None:
    """One decode dispatch per engine, and the encoder and fused route for
    every wave size the micro-batcher can close (1..max_batch)."""
    import numpy as np
    from repro.serving.engine import Request
    for name, eng in engines.items():
        req = Request(uid=-1, prompt_tokens=np.array([1], np.int32),
                      max_new_tokens=1)
        eng.run_until_drained([req])
        if not (req.done and len(req.output_tokens) == 1):
            raise RuntimeError(f"{name}: warm-up request did not finish")
    texts = [f"warm up request {i}" for i in range(gw.batcher.max_batch)]
    for b in range(1, len(texts) + 1):
        gw.service.submit_texts(texts[:b], max_new_tokens=1)


def count_compiles() -> collections.Counter:
    """From now on, count XLA compilations (``backend_compile``) and the
    persistent cache's lookups and hits, through `jax.monitoring`."""
    from jax import monitoring
    seen = collections.Counter()
    events = {"/jax/compilation_cache/compile_requests_use_cache": "lookups",
              "/jax/compilation_cache/cache_hits": "hits"}

    def on_event(name, **_):
        if name in events:
            seen[events[name]] += 1

    def on_duration(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            seen["compiles"] += 1
            seen["compile_s"] += secs

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    return seen


def base_model_name(svc) -> str:
    """``repro/<family><k>[-ivf|-ivfpq]``: the served name without the
    router's construction keys, to which each request adds ``@lam=``."""
    from repro.core.routers.spec import RouterSpec, format_spec, parse_spec
    s = parse_spec(svc.spec)
    return "repro/" + format_spec(RouterSpec(s.family, k=s.k, ivf=s.ivf,
                                             pq=s.pq))


def start(config: dict, cache_dir: Path):
    """Engines, router, gateway, warm-up, and one request served over HTTP.
    Returns (engines, gateway, served model name, seconds per stage)."""
    from .loadgen import stream
    timings = {}
    t0 = time.perf_counter()
    engines = build_engines(config)
    timings["engines_s"] = time.perf_counter() - t0
    svc = router_service(config, engines, cache_dir, timings)
    gw = gateway(config, svc)
    t0 = time.perf_counter()
    warm_up(gw, engines)
    gw.start()
    model = base_model_name(svc)
    warm = stream(gw.port, model, {"i": -1, "due": time.perf_counter(),
                                   "text": "warm up", "lam": 0.0,
                                   "max_tokens": 2}, 300.0)
    if warm["status"] != "ok":
        gw.close()
        raise RuntimeError(f"warm-up request failed: {warm['error']}")
    timings["warm_up_s"] = time.perf_counter() - t0
    return engines, gw, model, timings
