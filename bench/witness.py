"""Witness of how the program's serving engine continues a prompt, beside
its own full-sequence forward pass; the benchmark's runs never run this.

    python bench/witness.py --config qwen3-mamba2.ivfpq100k [--tokens 8]

For each engine of the configuration, at the configuration's sizes and
on whatever device JAX finds, it serves greedy completions through
`repro.serving.engine.ServingEngine` in four ways and reads each with
the full-sequence forward `repro.models.model.forward`:

* ``first``: prompt A on a fresh engine, the first this process builds
  for the model (its decode step compiles on this request);
* ``alone``: the same again, on another fresh engine;
* ``shared``: prompt A admitted together with prompt B, into one wave;
* ``reused``: prompt A in a slot that served prompt B before;
Each is read by the full forward over A followed by the served tokens
(``gap_plain``) and over A with its last token written twice
(``gap_doubled``): the widest gap between the largest logit at a position
and the logit of the token served there.  A sound engine reads a
``gap_plain`` of rounding alone (its weights are bf16) in all four ways.
One JSON line per engine.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import boot, spec  # noqa: E402


def gap(fwd, params, prompt, tokens, width=32):
    """The widest gap, under the full forward over ``prompt`` followed by
    ``tokens``, between the largest logit at a position and the logit of
    the token served there.  One row of ``width`` tokens, zero-padded at
    the end (causal, so the padding changes nothing before it)."""
    import jax.numpy as jnp
    import numpy as np
    row = np.zeros((1, width), np.int32)
    seq = list(prompt) + list(tokens[:-1])
    row[0, :len(seq)] = seq
    lg = np.asarray(fwd(params, jnp.asarray(row)))[0]
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    return float(np.max(lg[at].max(-1) - lg[at, np.asarray(tokens)]))


def served(cfg, params, engine_kw, prompts, n, reuse=None):
    """Tokens the engine serves for ``prompts`` admitted together (after
    serving ``reuse`` alone in the same engine, where given)."""
    import numpy as np
    from repro.serving.engine import Request, ServingEngine
    eng = ServingEngine(cfg, params=params, **engine_kw)
    if reuse is not None:
        eng.run_until_drained([Request(uid=-1, prompt_tokens=np.asarray(
            reuse, np.int32), max_new_tokens=n)])
    reqs = [Request(uid=i, prompt_tokens=np.asarray(p, np.int32),
                    max_new_tokens=n) for i, p in enumerate(prompts)]
    eng.run_until_drained(reqs)
    return [list(r.output_tokens) for r in reqs]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--config", required=True,
                    help="a file name under bench/configs, without .json")
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--config-dir", default=str(BENCH / "configs"))
    args = ap.parse_args(argv)
    boot.program_path()
    import jax
    from repro.configs.base import ModelConfig
    from repro.models import model as M
    from harness import route_ref
    config = spec.load_json(Path(args.config_dir) / f"{args.config}.json")
    kw = {"max_slots": int(config["engine"]["max_slots"]),
          "cache_len": int(config["engine"]["cache_len"])}
    a_text = "python programming explain the code in detail"
    b_text = "world history compare two empires and their rules"
    for e in config["pool"]:
        vocab = int(e["config"]["vocab_size"])
        a = route_ref.prompt_tokens(a_text, vocab).tolist()
        b = route_ref.prompt_tokens(b_text, vocab).tolist()
        cfg = boot.arch_module(e["arch"]).program_config(
            e["config"], ModelConfig, e["name"])
        params = boot.make_weights(e)
        fwd = jax.jit(lambda p, t: M.forward(p, cfg, {"tokens": t})[0])
        row = {"engine": e["name"], "device": jax.devices()[0].device_kind}
        for way, toks in (
                ("first", served(cfg, params, kw, [a], args.tokens)[0]),
                ("alone", served(cfg, params, kw, [a], args.tokens)[0]),
                ("shared", served(cfg, params, kw, [a, b], args.tokens)[0]),
                ("reused", served(cfg, params, kw, [a], args.tokens,
                                  reuse=b)[0])):
            row[way] = {"tokens": toks,
                        "gap_plain": gap(fwd, params, a, toks),
                        "gap_doubled": gap(fwd, params, a + a[-1:], toks)}
        print(json.dumps(row), flush=True)
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
