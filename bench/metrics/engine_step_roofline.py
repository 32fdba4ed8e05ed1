"""The serving engines' jitted step (``engine_step``) as a share of the
chip's HBM roofline, in percent, from the profiler trace.

The numerator is the bytes each engine must move in one dispatch of its
step, times its dispatches in the window (``prefill_steps +
decode_steps`` of its stats: a replay of prompts or a decode wave alike).
The denominator is the device time of the programs named
``jit_engine_step`` in the traced window (both engines' steps) times the
chip's ``hbm_bytes_per_s``.

The bytes are a lower bound, from the pool's published ``config``, so
that the share cannot pass 100%:

* every parameter but the embedding table (the step gathers only its
  tokens' rows), in bf16, read once per dispatch;
* for a state-space model, one served slot's ``conv`` and ``ssd`` state
  per replay dispatch, and per decode wave the slots that produced a token
  (``tokens_out``), each read once and written once in float32.

KV-cache reads and writes, activations and the logits are left out.  A run
without a trace, or whose engines keep no such counters or name no step
``engine_step`` (a program that predates them), reads nothing."""
from harness import boot

BF16, F32 = 2, 4
PROGRAM = "jit_engine_step"


def weight_bytes(engine: dict) -> int:
    """Bytes of the parameters a dispatch reads: all but the embedding."""
    arch = boot.arch_module(engine["arch"])
    d = arch.dims(engine["config"])
    return BF16 * (arch.param_count(engine["config"]) - d["V"] * d["D"])


def state_bytes(engine: dict) -> int:
    """Bytes of one slot's recurrent state, read and written once (0 for
    an architecture that keeps none)."""
    if engine["arch"] != "mamba2":
        return 0
    d = boot.arch_module("mamba2").dims(engine["config"])
    conv = (d["K"] - 1) * (d["d_in"] + 2 * d["G"] * d["N"])
    ssd = d["H"] * d["P"] * d["N"]
    return 2 * F32 * d["L"] * (conv + ssd)


def step_seconds(trace) -> float:
    """Device seconds of the engines' step programs in the trace."""
    if trace is None:
        return 0.0
    return sum(s for name, s in trace.get("device_ops", [])
               if name.split("(")[0] == PROGRAM)


def read(run):
    seconds = step_seconds(run.trace)
    if not seconds:
        return None
    pool = {e["name"]: e for e in run.config.get("pool", [])}
    moved = 0.0
    for e in run.engines:
        st = e["delta"]
        if "prefill_steps" not in st:
            return None
        spec = pool[e["name"]]
        moved += (weight_bytes(spec) * (st["prefill_steps"]
                                        + st["decode_steps"])
                  + state_bytes(spec) * (st["prefill_steps"]
                                         + st["tokens_out"]))
    return 100.0 * moved / (seconds * run.peaks["hbm_bytes_per_s"])
