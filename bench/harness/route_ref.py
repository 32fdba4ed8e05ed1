"""Plain reference of the routing decision: tokenizer, query encoder,
support set, exact k-nearest-neighbour utility and the per-request-lambda
choice.  Nothing here imports the program; it restates the program's
definitions from their description:

* tokens: each lower-cased word's MD5 (first 8 hex digits) modulo 8190,
  plus 2, at most 64 words, zero-padded to 64; a served prompt is the first
  16 of them, modulo the engine's vocabulary;
* the query encoder: a 2-layer pre-norm decoder (768 wide, 12 heads of 64,
  SwiGLU 1536, RMSNorm eps 1e-6, rotary embeddings base 10000, causal),
  weights drawn from ``PRNGKey(7)``, mean-pooled over the non-zero tokens
  with no final norm, then scaled to unit length;
* the support set: text ``i`` is ``"<topic i mod 5> question i"``; from a
  NumPy generator seeded with the configuration's seed, each topic's
  affinity to each model is uniform in [0.2, 1], a row's scores are its
  topic's affinities plus normal noise (0.05) clipped to [0, 1], and each
  model's cost is uniform in [0.001, 0.01]; the router holds the rows of a
  70% split drawn by a generator seeded with 0;
* the decision: the ``k`` support rows of highest cosine similarity, the
  mean of their scores and costs, and the model of highest
  ``score - lam * cost``.
"""
from __future__ import annotations

import hashlib
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from .traffic import TOPICS

VOCAB = 8192
MAXLEN = 64
PROMPT = 16
WIDTH, HEADS, FF, LAYERS = 768, 12, 1536, 2
EPS, THETA = 1e-6, 10000.0
ENCODER_SEED = 7
CHUNK = 1024


def tokenize(text: str) -> np.ndarray:
    toks = [int(hashlib.md5(w.encode()).hexdigest()[:8], 16) % (VOCAB - 2)
            + 2 for w in text.lower().split()[:MAXLEN]] or [1]
    out = np.zeros(MAXLEN, np.int32)
    out[:len(toks)] = toks
    return out


def prompt_tokens(text: str, vocab: int) -> np.ndarray:
    return tokenize(text)[:PROMPT] % vocab


def _tn(key, shape, std):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * std


def encoder_weights():
    """The encoder's weights from its seed: the embedding from the first of
    eight subkeys, each layer from the first of three subkeys of the third,
    split once per layer."""
    ks = jax.random.split(jax.random.PRNGKey(ENCODER_SEED), 8)
    embed = _tn(ks[0], (VOCAB, WIDTH), 1.0)
    group_keys = jax.random.split(jax.random.split(ks[2], 3)[0], LAYERS)
    layers = []
    for gk in group_keys:
        lk = jax.random.split(jax.random.split(gk, 1)[0], 6)
        a = jax.random.split(lk[0], 4)
        m = jax.random.split(lk[3], 3)
        s = WIDTH ** -0.5
        layers.append({"wq": _tn(a[0], (WIDTH, WIDTH), s),
                       "wk": _tn(a[1], (WIDTH, WIDTH), s),
                       "wv": _tn(a[2], (WIDTH, WIDTH), s),
                       "wo": _tn(a[3], (WIDTH, WIDTH), s),
                       "w_gate": _tn(m[0], (WIDTH, FF), s),
                       "w_up": _tn(m[1], (WIDTH, FF), s),
                       "w_down": _tn(m[2], (FF, WIDTH), FF ** -0.5)})
    return {"embed": embed, "layers": layers}


#: how the reference computes: ``float32`` at the highest matmul precision
#: is the reference; the others are controls, one or two steps below the
#: precision a configuration states
MODES = ("float32", "bfloat16", "float8")


def fp8(x, axis=-1):
    """``x`` rounded to fp8 (e4m3), one scale per slice along ``axis``
    mapping its largest magnitude to 448, returned in bfloat16."""
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * scale).astype(jnp.bfloat16)


def _mm(mode, spec, a, b, b_axis=-2):
    """``einsum(spec, a, b)``: float32 at the highest precision; bfloat16
    operands; or fp8 operands (``a`` scaled along its last axis, ``b``
    along ``b_axis``) multiplied in bfloat16.  Accumulates in float32."""
    if mode == "float32":
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    if mode == "float8":
        a, b = fp8(a), fp8(b, b_axis)
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _norm(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)


def encode(w, tokens, mode="float32"):
    """Pooled embeddings (B, 768) of token rows (B, 64), unnormalised.
    ``bfloat16`` keeps weights and activations in bfloat16; ``float8``
    rounds both operands of every matmul to fp8."""
    act = jnp.bfloat16 if mode == "bfloat16" else jnp.float32
    mm = lambda s, a, b: _mm(mode, s, a, b).astype(act)  # noqa: E731
    B, S = tokens.shape
    hd = WIDTH // HEADS
    pos = jnp.arange(S, dtype=jnp.float32)
    inv = 1.0 / THETA ** (jnp.arange(hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = pos[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]

    def rope(t):
        t1, t2 = t[..., :hd // 2], t[..., hd // 2:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                               -1).astype(act)

    causal = jnp.tril(jnp.ones((S, S), bool))
    x = w["embed"].astype(act)[tokens]
    for lp in w["layers"]:
        h = _norm(x.astype(jnp.float32)).astype(act)
        q = rope(mm("bsd,de->bse", h, lp["wq"]).reshape(B, S, HEADS, hd))
        k = rope(mm("bsd,de->bse", h, lp["wk"]).reshape(B, S, HEADS, hd))
        v = mm("bsd,de->bse", h, lp["wv"]).reshape(B, S, HEADS, hd)
        s = _mm(mode, "bqhd,bkhd->bhqk", q, k, -1) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = _mm(mode, "bhqk,bkhd->bqhd", p.astype(act), v, -3)
        x = (x + mm("bse,ed->bsd", o.astype(act).reshape(B, S, WIDTH),
                    lp["wo"])).astype(act)
        h = _norm(x.astype(jnp.float32)).astype(act)
        a = (jax.nn.silu(mm("bsd,df->bsf", h, lp["w_gate"]))
             * mm("bsd,df->bsf", h, lp["w_up"])).astype(act)
        x = (x + mm("bsf,fd->bsd", a, lp["w_down"])).astype(act)
    mask = (tokens > 0).astype(jnp.float32)[..., None]
    return ((x.astype(jnp.float32) * mask).sum(1)
            / jnp.maximum(mask.sum(1), 1.0))


def embed(w, texts, mode="float32") -> np.ndarray:
    """Unit-length embeddings of ``texts``, in chunks of `CHUNK` rows."""
    run = jax.jit(lambda w, t: encode(w, t, mode))
    toks = np.stack([tokenize(t) for t in texts])
    out = []
    for i in range(0, len(toks), CHUNK):
        block = toks[i:i + CHUNK]
        # fixed shapes: full chunks, or a short list padded to 64 rows
        pad = (CHUNK if len(toks) > CHUNK
               else -(-len(block) // 64) * 64) - len(block)
        if pad:
            block = np.concatenate([block, np.zeros((pad, MAXLEN),
                                                    np.int32)])
        e = np.asarray(run(w, jnp.asarray(block)))[:len(toks[i:i + CHUNK])]
        out.append(e)
    emb = np.concatenate(out).astype(np.float64)
    emb /= np.maximum(np.linalg.norm(emb, axis=1, keepdims=True), 1e-9)
    return emb.astype(np.float32)


def support(n_texts: int, n_models: int, seed: int):
    """(texts, scores, costs) of the router's rows, in row order."""
    rng = np.random.default_rng(seed)
    texts = [f"{TOPICS[i % len(TOPICS)]} question {i}" for i in range(n_texts)]
    affinity = rng.uniform(0.2, 1.0, (len(TOPICS), n_models))
    topic = np.arange(n_texts) % len(TOPICS)
    scores = np.clip(affinity[topic] + rng.normal(0, 0.05,
                                                  (n_texts, n_models)), 0, 1)
    costs = np.tile(rng.uniform(0.001, 0.01, n_models), (n_texts, 1))
    rows = np.sort(np.random.default_rng(0).permutation(n_texts)
                   [:int(0.7 * n_texts)])
    return ([texts[i] for i in rows], scores[rows].astype(np.float32),
            costs[rows].astype(np.float32))


class Router:
    """The reference router over a configuration's support set, computed
    in ``mode``.  With ``cache_dir`` its support embeddings are kept there
    (``support-<rows>-<mode>.npy``) once computed: they are the
    reference's own, made from the seed, and the same in every run."""

    def __init__(self, n_texts: int, models, seed: int, k: int,
                 mode: str = "float32", cache_dir=None):
        if mode not in MODES:
            raise ValueError(f"unknown mode {mode!r}")
        self.models = list(models)
        self.k = k
        self.mode = mode
        self.w = encoder_weights()
        texts, self.S, self.C = support(n_texts, len(self.models), seed)
        path = (None if cache_dir is None else
                Path(cache_dir) / f"support-{len(texts)}-{mode}.npy")
        if path is not None and path.is_file():
            X = np.load(path)
        else:
            X = embed(self.w, texts, mode)
            if path is not None:
                path.parent.mkdir(parents=True, exist_ok=True)
                tmp = path.with_suffix(".tmp.npy")
                np.save(tmp, X)
                tmp.rename(path)
        self.X = jnp.asarray(X)

    def answers(self, texts, lams) -> dict:
        """For each text: its unit embedding ``emb`` (Q, 768), the
        neighbours' mean scores ``s_hat`` (Q, M) and the utilities
        ``util`` = ``s_hat - lam * c_hat`` (Q, M)."""
        q = embed(self.w, list(texts), self.mode)
        sims = jax.jit(lambda q, x: _mm(self.mode, "qd,nd->qn", q, x, -1))
        out = []
        for i in range(0, len(q), CHUNK):
            _, idx = jax.lax.top_k(sims(jnp.asarray(q[i:i + CHUNK]),
                                        self.X), self.k)
            out.append(np.asarray(idx))
        idx = np.concatenate(out) if out else np.zeros((0, self.k), int)
        s_hat = self.S[idx].mean(axis=1)
        c_hat = self.C[idx].mean(axis=1)
        util = s_hat - np.asarray(lams, np.float32)[:, None] * c_hat
        return {"emb": q, "s_hat": s_hat, "util": util}

    def utilities(self, texts, lams) -> np.ndarray:
        """(Q, M) utilities ``s_hat - lam * c_hat``."""
        return self.answers(texts, lams)["util"]
