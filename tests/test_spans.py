"""Spans of the routed hop (`repro.spans`): `embed_texts` and
`RouterService.route_fused` mark their stages as profiler annotations,
nested as documented and carrying the batch size, on every branch of
`KNNRouter.serve_fused`, without changing a bit of the answers and
without a host sync that the R1 lint would flag."""
import glob
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.analysis.lint import lint_paths
from repro.core.dataset import RoutingDataset
from repro.core.routers.knn import KNNRouter
from repro.serving import encoder
from repro.serving.router_service import RouterService

SRC = Path(__file__).resolve().parent.parent / "src"
MODELS = ["m-a", "m-b", "m-c"]
WAVE = 8
CHUNK = encoder._CHUNK


@pytest.fixture(scope="module")
def ds():
    texts = [f"topic {i % 5} example {i}" for i in range(160)]
    emb = encoder.embed_texts(texts)
    rng = np.random.default_rng(0)
    return RoutingDataset(
        "spans", emb,
        rng.uniform(0.2, 1.0, (160, 3)).astype(np.float32),
        rng.uniform(0.001, 0.01, (160, 3)).astype(np.float32), MODELS)


def service(ds, **kw):
    return RouterService(KNNRouter(k=7, **kw).fit(ds),
                         {n: None for n in MODELS}, lam=0.5)


def wave_texts():
    return [f"question number {i} about topic {i % 3}" for i in range(WAVE)]


def traced(fn, log_dir):
    """``fn()``'s result and the program spans it left in the trace, as
    ``(line id, name, start_ns, end_ns, stats)``, found by name on every
    host line (on the CPU the main thread's line is ``python``)."""
    with jax.profiler.trace(str(log_dir)):
        out = fn()
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.split("/")[0] in ("encode", "route"):
                    spans.append(((plane.name, line.name), ev.name,
                                  ev.start_ns, ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    return out, spans


def children(spans, parent):
    """The spans nested inside ``parent`` on its line."""
    line, _, s, e, _ = parent
    return [x for x in spans if x is not parent and x[0] == line
            and s <= x[2] and x[3] <= e]


def named(spans, name):
    return [x for x in spans if x[1] == name]


def old_embed(texts):
    """`embed_texts` as it was before its stages were spanned."""
    toks = np.stack([encoder.hash_tokenize(t) for t in texts])
    run = encoder._encoder()
    emb = np.concatenate([np.asarray(run(jnp.asarray(toks[i:i + CHUNK])))
                          for i in range(0, len(toks), CHUNK)])
    emb /= np.maximum(np.linalg.norm(emb, axis=1, keepdims=True), 1e-9)
    return emb.astype(np.float32)


def test_encode_spans_nest_with_rows(tmp_path):
    texts = wave_texts()
    emb, spans = traced(lambda: encoder.embed_texts(texts), tmp_path)
    assert emb.shape == (WAVE, 768)
    enc, = named(spans, "encode")
    assert enc[4]["rows"] == WAVE
    kids = children(spans, enc)
    assert sorted(x[1] for x in kids) == ["encode/dispatch", "encode/fetch",
                                          "encode/tokenize"]
    assert all(x[4]["rows"] == WAVE for x in kids)
    tok, = named(kids, "encode/tokenize")
    disp, = named(kids, "encode/dispatch")
    fetch, = named(kids, "encode/fetch")
    # tokenize, then the chunk's dispatch, then its fetch
    assert tok[3] <= disp[2] and disp[3] <= fetch[2]


@pytest.mark.parametrize("branch", ["fused", "tail", "sharded"])
def test_route_spans_nest_with_rows_on_every_branch(ds, branch, tmp_path):
    """``route`` wraps `route_fused`; its children ``route/dispatch`` and
    ``route/fetch`` come from `serve_fused` on the fused, tail-only and
    batch-sharded branches alike, and the fetch copies one buffer."""
    from jax.sharding import Mesh
    svc = service(ds, index="exact",
                  **({"backend": "host"} if branch == "tail" else {}))
    assert (svc.router.resolve_backend(WAVE) == "host") == (branch == "tail")
    mesh = (Mesh(np.array(jax.devices()[:1]), ("q",))
            if branch == "sharded" else None)
    X = ds.part("test")[0][:WAVE]
    lam = np.linspace(0.0, 2.0, WAVE).astype(np.float32)
    svc.route_fused(X, lam, qmesh=mesh)       # compiled outside the trace
    _, spans = traced(lambda: svc.route_fused(X, lam, qmesh=mesh), tmp_path)
    route, = named(spans, "route")
    assert route[4]["rows"] == WAVE
    kids = children(spans, route)
    assert sorted(x[1] for x in kids) == ["route/dispatch", "route/fetch"]
    assert all(x[4]["rows"] == WAVE for x in kids)
    disp, = named(kids, "route/dispatch")
    fetch, = named(kids, "route/fetch")
    assert disp[3] <= fetch[2]
    assert fetch[4]["buffers"] == 1


def test_answers_are_bitwise_those_of_the_unspanned_calls(ds, tmp_path):
    """Traced and untraced, `embed_texts` answers as its pre-span form and
    `route_fused` as the legacy chain, its bitwise parity oracle."""
    texts = wave_texts()
    want = old_embed(texts)
    svc = service(ds, index="exact")
    lam = np.linspace(0.0, 2.0, WAVE).astype(np.float32)
    ref = svc.route_legacy(want, lam)

    def wave():
        emb = encoder.embed_texts(texts)
        return emb, svc.route_fused(emb, lam)

    for emb, got in (wave(), traced(wave, tmp_path)[0]):
        np.testing.assert_array_equal(emb, want)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(a, b)


def test_spans_add_no_host_sync_finding_or_pragma():
    """The R1 lint over ``src/`` stays clean, and the span helper needs no
    ``allow-host`` pragma."""
    active, suppressed = lint_paths(SRC, rules=["R1"])
    assert [f.render() for f in active] == []
    assert "allow-host" not in (SRC / "repro" / "spans.py").read_text()
