"""The comparison that decides ``correct``.

Each layer against its plain reference, on what the timed path itself
answered:

* the served engines (the gateway's cells): a sample of the completed
  requests drawn from the seed, with the longest and one of each engine
  that served any among them, until it holds the mix's ``check_tokens``
  served tokens.  The reference runs once over each prompt followed by its
  served tokens, and each served token's logit is compared with the
  reference's largest at its position.  Per engine, the number is the
  widest such gap (``logit_gap.<engine>``).  Greedy decoding makes a gap
  of 0 the exact answer; bf16 rounding leaves small ones.
* the routing answers: for each request (every completed one in the
  gateway's cells, a sample drawn from the seed in the routing cells) the
  reference router's embedding, predicted scores and utilities at its
  ``lam`` against the program's: ``route_regret_mean``, and where the
  answer carries them ``score_gap_mean`` and ``embed_gap``
  (`route_numbers`).

`bench/control.py` puts the control (the references one precision step
below the configured one) in the program's place in the same comparison.
"""
from __future__ import annotations

import random
from typing import Dict, List, Optional

import numpy as np

from . import route_ref


def sample(records: List[Dict], seed: int, tokens: int) -> List[Dict]:
    """Completed requests drawn from ``seed``: the one with the most served
    tokens first, then one of each engine that served any and is not in
    yet, then others at random until ``tokens`` served tokens."""
    done = [r for r in records if r["status"] == "ok" and r["tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r["tokens"]), -r["i"]))
    rest = [r for r in done if r is not longest]
    random.Random(int(seed) ^ 0x5EED).shuffle(rest)
    out, n = [longest], len(longest["tokens"])
    for r in list(rest):
        if r["served_by"] not in {x["served_by"] for x in out}:
            out.append(r)
            rest.remove(r)
            n += len(r["tokens"])
    for r in rest:
        if n >= tokens:
            break
        out.append(r)
        n += len(r["tokens"])
    return out


def sample_routed(records: List[Dict], seed: int, n: int) -> List[Dict]:
    """``n`` routed requests drawn from ``seed``, each text once."""
    seen, uniq = set(), []
    for r in records:
        if r["status"] == "ok" and r["text"] not in seen:
            seen.add(r["text"])
            uniq.append(r)
    rng = random.Random(int(seed) ^ 0x5EED)
    return rng.sample(uniq, min(n, len(uniq)))


#: token rows of one reference call: a fixed shape, so that every run
#: compiles (or loads) the same programs
ROWS, WIDTH = 8, 96


def sequences(recs: List[Dict], vocab: int, rows_: int = ROWS,
              width: int = WIDTH):
    """Token rows ``prompt + served[:-1]``, zero-padded at the end to
    ``width`` and with empty rows to ``rows_``, and the served tokens with
    the positions whose logits predict them."""
    rows, served, where = [], [], []
    for r in recs:
        p = route_ref.prompt_tokens(r["text"], vocab)
        s = np.asarray(r["tokens"], np.int64)
        rows.append(np.concatenate([p, s[:-1]]))
        served.append(s)
        where.append(np.arange(len(p) - 1, len(p) - 1 + len(s)))
    width = max([width] + [len(x) for x in rows])
    toks = np.zeros((max(rows_, len(rows)), width), np.int32)
    for i, x in enumerate(rows):
        toks[i, :len(x)] = x
    return toks, served, where


def engine_gaps(arch, hf: dict, params, recs: List[Dict],
                control: bool = False, batch: int = ROWS) -> np.ndarray:
    """Gaps, under the float32 reference, of the served tokens (or, with
    ``control``, of the tokens the control's logits put first at the same
    positions)."""
    import jax
    import jax.numpy as jnp
    ref = jax.jit(lambda p, t: arch.forward(p, hf, t, "reference"))
    low = jax.jit(lambda p, t: arch.forward(p, hf, t, "control"))
    out = []
    for i in range(0, len(recs), batch):
        toks, served, where = sequences(recs[i:i + batch], hf_vocab(hf))
        lg = ref(params, jnp.asarray(toks))
        picks = low(params, jnp.asarray(toks)) if control else None
        lg = np.asarray(lg)
        for j, (s, w) in enumerate(zip(served, where)):
            rows = lg[j, w]
            tok = (np.asarray(jnp.argmax(picks[j, w], -1)) if control
                   else s)
            out.append(rows.max(-1) - rows[np.arange(len(w)), tok])
    return np.concatenate(out) if out else np.zeros(0)


def hf_vocab(hf: dict) -> int:
    return int(hf["vocab_size"])


def route_numbers(ref: Dict, recs: List[Dict], answers: Dict) -> Dict:
    """The routing answers of ``recs`` against the reference's (``ref``,
    from `route_ref.Router.answers` over the same texts and lambdas):

    * ``route_regret_mean``: the mean regret, the reference's best utility
      less that of the model chosen;
    * ``score_gap_mean``: the mean over the requests of the widest gap
      between a predicted score (the neighbours' mean score of a model)
      and the reference's;
    * ``embed_gap``: the widest ``1 - cos`` between a query's embedding and
      the reference's.

    Scores and choices are compared by their means, not their widest gaps:
    the support set's texts differ by one token, so the 100 nearest rows
    of a query are drawn from many rows at nearly the same similarity,
    and any rounding swaps some of them.  The widest gap over a sample is
    then the same for the program and for a control one precision step
    down; the mean is steady from seed to seed and tells them apart.

    ``answers`` holds what was answered: ``choice`` (model indices), and,
    where the answer carries them, ``s_hat`` and ``emb``."""
    util = ref["util"]
    out = {"route_regret_mean": float(np.mean(
        util.max(1) - util[np.arange(len(recs)), answers["choice"]]))}
    if answers.get("s_hat") is not None:
        out["score_gap_mean"] = float(np.mean(np.abs(
            np.asarray(answers["s_hat"]) - ref["s_hat"]).max(1)))
    if answers.get("emb") is not None:
        e = np.asarray(answers["emb"], np.float64)
        e /= np.maximum(np.linalg.norm(e, axis=1, keepdims=True), 1e-12)
        out["embed_gap"] = float(np.max(
            1.0 - np.sum(e * ref["emb"].astype(np.float64), axis=1)))
    return out


def served_answers(router: "route_ref.Router", recs: List[Dict]) -> Dict:
    """What the program answered for ``recs``, in `route_numbers`' form."""
    out = {"choice": np.asarray([router.models.index(r["served_by"])
                                 for r in recs])}
    if recs and "s_row" in recs[0]:
        out["s_hat"] = np.asarray([r["s_row"] for r in recs])
    if recs and "emb" in recs[0]:
        out["emb"] = np.stack([np.asarray(r["emb"]) for r in recs])
    return out


def control_answers(ctrl: "route_ref.Router", recs: List[Dict]) -> Dict:
    """What the control (the reference one precision step down, in the
    program's place) answers for ``recs``."""
    a = ctrl.answers([r["text"] for r in recs], [r["lam"] for r in recs])
    full = bool(recs) and "s_row" in recs[0]
    return {"choice": a["util"].argmax(1),
            "s_hat": a["s_hat"] if full else None,
            "emb": a["emb"] if full else None}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """Each compared number beside its limit; a number with no limit, or a
    limit with no number, fails."""
    out = {}
    for name in sorted(set(numbers) | set(limits)):
        v, lim = numbers.get(name), limits.get(name)
        out[name] = {"value": v, "limit": lim,
                     "ok": v is not None and lim is not None
                     and bool(v <= lim)}
    return out
