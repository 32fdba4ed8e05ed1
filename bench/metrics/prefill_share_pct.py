"""The engines' prompt replay as a share of their serving time, in
percent: ``prefill_s`` over ``prefill_s + decode_s``, the changes over the
window summed over the engines.  A replay is a whole decode dispatch per
prompt position, shared by the prompts admitted together (the
teacher-forced prefill of `ServingEngine`), so this reads what prefill
costs beside decode.  A run whose engines keep no such
counters (a program that predates them) reads nothing."""


def read(run):
    pre = total = 0.0
    for e in run.engines:
        st = e["delta"]
        if "prefill_s" not in st or "decode_s" not in st:
            return None
        pre += st["prefill_s"]
        total += st["prefill_s"] + st["decode_s"]
    if not total:
        return None
    return 100.0 * pre / total
