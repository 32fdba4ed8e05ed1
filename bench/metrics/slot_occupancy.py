"""Share of decode slots that produced a token, in percent: the engines'
``tokens_out`` over ``decode_steps`` times ``max_slots``, changes over the
window summed over the engines.  The engines count only the decode steps
of `ServingEngine.step`, not the dispatches that replay a prompt."""


def read(run):
    steps = sum(e["delta"]["decode_steps"] * e["max_slots"]
                for e in run.engines)
    if not steps:
        return None
    return 100.0 * sum(e["delta"]["tokens_out"] for e in run.engines) / steps
