"""Deterministic query embedder: hash tokenizer + tiny JAX transformer
encoder, mean-pooled.  Stands in for the paper's BERT embedding service
(offline container) — 768-d, L2-normalizable, fully seeded."""
from __future__ import annotations

import hashlib
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN_DENSE, ModelConfig
from repro.models import model as M
from repro.spans import span

_VOCAB = 8192
_MAXLEN = 64
#: texts per encoder dispatch: bounds the activations a support-set build
#: (1e5+ texts) holds on the device at once
_CHUNK = 2048


def hash_tokenize(text: str, max_len: int = _MAXLEN) -> np.ndarray:
    toks = []
    for w in text.lower().split()[:max_len]:
        h = int(hashlib.md5(w.encode()).hexdigest()[:8], 16)
        toks.append(h % (_VOCAB - 2) + 2)
    if not toks:
        toks = [1]
    out = np.zeros(max_len, np.int32)
    out[: len(toks)] = toks[: max_len]
    return out


@lru_cache(maxsize=1)
def _encoder():
    cfg = ModelConfig(
        name="query-encoder", arch_type="dense", n_layers=2, d_model=768,
        n_heads=12, n_kv_heads=12, d_ff=1536, vocab_size=_VOCAB,
        pattern=(ATTN_DENSE,), n_groups=2, dtype="float32", remat=False)
    params = M.init_params(jax.random.PRNGKey(7), cfg)

    @jax.jit
    # repro: allow-jit-cache: _encoder is lru_cached, one cache per process
    def query_encoder(params, tokens):
        x = params["embed"][tokens]
        pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
        from repro.models import transformer as tfm
        h, _ = tfm.stack_full(params["stack"], cfg, x, pos)
        mask = (tokens > 0).astype(jnp.float32)[..., None]
        pooled = (h * mask).sum(1) / jnp.maximum(mask.sum(1), 1.0)
        return pooled

    # the weights go in as an argument: closed over, they would be baked
    # into every compiled batch size as 67 MB of constants, and into each
    # of its persistent compile-cache entries
    return partial(query_encoder, params)


def embed_texts(texts) -> np.ndarray:
    """Unit-norm float32 embeddings of ``texts``, one row each.  Traced as
    the span ``encode`` with the children ``encode/tokenize``, and
    ``encode/dispatch`` then ``encode/fetch`` for each chunk
    (`repro.spans`)."""
    rows = len(texts)
    with span("encode", rows=rows):
        with span("encode/tokenize", rows=rows):
            toks = np.stack([hash_tokenize(t) for t in texts])
        run = _encoder()
        chunks = []
        # each chunk is fetched before the next is dispatched, so a support
        # set's build holds one chunk's outputs on the device at a time
        for i in range(0, len(toks), _CHUNK):
            part = toks[i:i + _CHUNK]
            with span("encode/dispatch", rows=len(part)):
                out = run(jnp.asarray(part))
            with span("encode/fetch", rows=len(part)):
                chunks.append(np.asarray(out))
        emb = np.concatenate(chunks)
        emb /= np.maximum(np.linalg.norm(emb, axis=1, keepdims=True), 1e-9)
        return emb.astype(np.float32)
