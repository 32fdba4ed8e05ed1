"""Model FLOP/s utilisation of the window, in percent of the chip's bf16
peak: for each engine, the prompt and output tokens it served in the window
(``prefill_tokens`` and ``tokens_out`` of its stats) times its forward
FLOPs per token from the published sizes (at half the cache length of
context), over the window's seconds times the peak of the ``device_kind``.
It counts the work, not the dispatches that did it."""


def read(run):
    flops = sum((e["delta"]["prefill_tokens"] + e["delta"]["tokens_out"])
                * e["flops_per_token"] for e in run.engines)
    if not flops:
        return None
    return 100.0 * flops / (run.seconds * run.chips
                            * run.peaks["bf16_flops_per_s"])
