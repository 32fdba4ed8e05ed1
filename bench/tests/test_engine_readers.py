"""The gateway cell's engine readers: `engine_step_roofline` (bytes a
dispatch must move, over the device time of the engines' step programs)
and `prefill_share_pct` (replay seconds over the engines' serving
seconds), on the run data a gateway run hands them."""
import json
from pathlib import Path

import pytest

import run
from harness import spec

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "qwen3-4b.mamba2-370m.ivfpq100k.json").read_text())
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ROOF = spec.load_reader("engine_step_roofline")
SHARE = spec.load_reader("prefill_share_pct")
STATS = {"qwen3-4b": {"prefill_steps": 150, "decode_steps": 259,
                      "tokens_out": 326, "prefill_s": 2.0, "decode_s": 4.5},
         "mamba2-370m": {"prefill_steps": 105, "decode_steps": 134,
                         "tokens_out": 153, "prefill_s": 0.4,
                         "decode_s": 0.8}}


def gateway_run(stats, device_ops):
    trace = (None if device_ops is None else
             {"busy_s": 7.0, "window_s": 10.0, "device_ops": device_ops})
    return run.Run(engines=[{"name": n, "delta": d} for n, d in stats.items()],
                   config=CONFIG, peaks=PEAKS, trace=trace)


def test_bytes_per_dispatch_are_the_published_sizes():
    from metrics import engine_step_roofline as m
    q, s = (next(e for e in CONFIG["pool"] if e["name"] == n)
            for n in ("qwen3-4b", "mamba2-370m"))
    # parameters but the embedding in bf16: about 8.04 GB and 0.74 GB
    assert m.weight_bytes(q) == pytest.approx(8.04e9, rel=0.01)
    assert m.weight_bytes(s) == pytest.approx(0.74e9, rel=0.02)
    assert m.state_bytes(q) == 0
    # 48 layers of conv and ssd state in float32, read and written
    assert m.state_bytes(s) == pytest.approx(101e6, rel=0.03)


def test_roofline_reads_the_step_programs_device_time():
    from metrics import engine_step_roofline as m
    ops = [["jit_engine_step(123)", 6.0], ["jit_engine_step(456)", 0.5],
           ["jit__serve_fused_jit(7)", 0.1], ["jit_greedy_tokens(8)", 0.01]]
    got = ROOF(gateway_run(STATS, ops))
    pool = {e["name"]: e for e in CONFIG["pool"]}
    moved = sum(m.weight_bytes(pool[n]) * (d["prefill_steps"]
                                           + d["decode_steps"])
                + m.state_bytes(pool[n]) * (d["prefill_steps"]
                                            + d["tokens_out"])
                for n, d in STATS.items())
    assert got == pytest.approx(100.0 * moved / (6.5 * 819e9))
    assert 0 < got <= 100.0


@pytest.mark.parametrize("ops", [None, [], [["jit__lambda(1)", 7.0]]])
def test_roofline_reads_nothing_without_the_step_program(ops):
    """Untraced, or a program whose step has another name, reads nothing."""
    assert ROOF(gateway_run(STATS, ops)) is None


def test_readers_read_nothing_from_engines_without_the_counters():
    old = {n: {"decode_steps": 9, "tokens_out": 9, "prefill_tokens": 9}
           for n in STATS}
    ops = [["jit_engine_step(1)", 6.0]]
    assert ROOF(gateway_run(old, ops)) is None
    assert SHARE(gateway_run(old, ops)) is None


def test_prefill_share_is_replay_over_serving_seconds():
    assert SHARE(gateway_run(STATS, None)) == pytest.approx(
        100.0 * 2.4 / 7.7)
