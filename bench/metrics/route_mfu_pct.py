"""Model FLOP/s utilisation of the routing window, in percent of the
chip's bf16 peak: every routed request's FLOPs over the seconds from the
window's start to its last wave's end, times the peak of the
``device_kind``.  A request's FLOPs are the query encoder's at its fixed
length (every request is padded to ``encoder.max_tokens``; projections,
SwiGLU and the causal half of the attention scores and mix) and the exact
scan of its embedding against the ``router.support_rows`` rows.  The
top-k, the neighbour means and the choice are not counted.  It counts
the work, not the dispatches that did it."""


def request_flops(config: dict) -> float:
    enc, r = config["encoder"], config["router"]
    s, h, f = (int(enc["max_tokens"]), int(enc["hidden_size"]),
               int(enc["intermediate_size"]))
    per_token = 8 * h * h + 6 * h * f + 2 * s * h
    return (s * int(enc["layers"]) * per_token
            + 2 * int(r["support_rows"]) * int(r["embedding_dim"]))


def read(run):
    if not run.waves:
        return None
    flops = len(run.records) * request_flops(run.config)
    return 100.0 * flops / ((run.t_end - run.t0) * run.chips
                            * run.peaks["bf16_flops_per_s"])
