"""The routing hop alone, as a routing service runs it: each wave of
requests is tokenized and embedded by the program's query encoder
(`repro.serving.encoder.embed_texts`) and routed by one fused dispatch
(`RouterService.route_fused`: retrieval, neighbour utility and the
per-request-``lam`` choice), and the answer (the chosen model and its
predicted score row) goes back to the caller, which calls that model
itself.  No engine is built: the pool's models are names.

A mix for this path says ``"endpoint": "route"`` and ``"wave": W``: a
closed loop that routes waves of ``W`` requests back to back, the next
as soon as the last one's answer is on the host.
"""
from __future__ import annotations

import gc
import time
from pathlib import Path
from typing import Dict, List

from . import boot


def start(config: dict, cache_dir: Path, wave: int):
    """The router service over the cached artifact, with the wave's
    encoder and route programs compiled (or loaded) by one warm-up wave.
    Returns (service, seconds per stage)."""
    timings = {}
    names = [e["name"] for e in config["pool"]]
    svc = boot.router_service(config, dict.fromkeys(names), cache_dir,
                              timings)
    t0 = time.perf_counter()
    texts = [f"warm up request {i}" for i in range(wave)]
    route_wave(svc, texts, [0.0] * wave)
    timings["warm_up_s"] = time.perf_counter() - t0
    return svc, timings


def route_wave(svc, texts: List[str], lams: List[float]):
    """One wave through the program: (choice indices, score rows,
    embeddings, seconds to embed, seconds to route)."""
    import numpy as np
    from repro.serving import encoder
    t0 = time.perf_counter()
    emb = encoder.embed_texts(texts)
    t1 = time.perf_counter()
    choice, s_hat, _, _, _ = svc.route_fused(emb, np.asarray(lams,
                                                             np.float32))
    t2 = time.perf_counter()
    return choice, s_hat, emb, t1 - t0, t2 - t1


def serve(svc, reqs: List[Dict], wave: int, seconds: float) -> Dict:
    """Routes waves of ``reqs`` back to back for ``seconds``: no wave
    starts after that, and the one under way then is finished and counted,
    so the window runs from ``t0`` to ``t_end``, the end of its last wave.
    The waves' texts and lambdas are laid out before the window and the
    records built after it, so that the window holds the program's work
    and little of the benchmark's; the heap is collected and frozen before
    it, so that no collection inside it walks the set-up's objects.
    Returns ``t0``, ``t_end``, one record per routed request, the waves'
    times and how often the schedule was wrapped round."""
    import numpy as np
    names = list(svc.model_names)
    n = len(reqs) - len(reqs) % wave
    batches = [([r["text"] for r in reqs[i:i + wave]],
                np.asarray([r["lam"] for r in reqs[i:i + wave]], np.float32))
               for i in range(0, n, wave)]
    done = []
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter()
    b = wraps = 0
    while True:
        start = time.perf_counter()
        if start >= t0 + seconds:
            break
        if b == len(batches):
            b, wraps = 0, wraps + 1
        choice, s_hat, emb, enc_s, route_s = route_wave(svc, *batches[b])
        done.append((b, start, time.perf_counter(), enc_s, route_s, choice,
                     s_hat, emb))
        b += 1
    gc.unfreeze()
    records, waves = [], []
    for b, start, end, enc_s, route_s, choice, s_hat, emb in done:
        waves.append({"start": start, "end": end, "encode_s": enc_s,
                      "route_s": route_s})
        for j, r in enumerate(reqs[b * wave:(b + 1) * wave]):
            records.append(dict(r, status="ok", due=start, end=end,
                                served_by=names[int(choice[j])],
                                s_row=s_hat[j], emb=emb[j]))
    return {"t0": t0, "t_end": waves[-1]["end"] if waves else t0,
            "records": records, "waves": waves, "wraps": wraps}
