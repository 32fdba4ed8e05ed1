"""Model FLOP/s utilisation of the whole served path, in percent of the
chip's bf16 peak of the ``device_kind``.

A routed request's FLOPs are the query encoder's at its fixed length
(every request is padded to ``encoder.max_tokens``; projections, SwiGLU
and the causal half of the attention scores and mix) and the exact scan of
its embedding against the ``router.support_rows`` rows.  The top-k, the
neighbour means and the choice are not counted.

In a routing cell: every routed request's FLOPs over the seconds from the
window's start to its last wave's end.  In a gateway cell: the router's
FLOPs for the requests answered, plus, for each engine, the prompt and
output tokens it served in the window (``prefill_tokens`` and
``tokens_out`` of its stats) times its forward FLOPs per token from the
published sizes (at half the cache length of context), over the window's
seconds.  It counts the work, not the dispatches that did it."""
from harness import stats


def request_flops(config: dict) -> float:
    enc, r = config["encoder"], config["router"]
    s, h, f = (int(enc["max_tokens"]), int(enc["hidden_size"]),
               int(enc["intermediate_size"]))
    per_token = 8 * h * h + 6 * h * f + 2 * s * h
    return (s * int(enc["layers"]) * per_token
            + 2 * int(r["support_rows"]) * int(r["embedding_dim"]))


def read(run):
    if run.waves is not None:
        if not run.waves:
            return None
        flops = len(run.records) * request_flops(run.config)
        return 100.0 * flops / ((run.t_end - run.t0) * run.chips
                                * run.peaks["bf16_flops_per_s"])
    flops = (sum(stats.ok(r) for r in run.records) * request_flops(run.config)
             + sum((e["delta"]["prefill_tokens"] + e["delta"]["tokens_out"])
                   * e["flops_per_token"] for e in run.engines))
    if not flops:
        return None
    return 100.0 * flops / (run.seconds * run.chips
                            * run.peaks["bf16_flops_per_s"])
