"""Each pool model's decode step at its published widths, compiled for a
described TPU v5e chip without one: what the chip's compiler would refuse,
and the bytes it reports (weights, cache and temporaries)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

CONFIGS = BENCH / "configs"
GIB = 2 ** 30


def pool():
    seen = {}
    for path in sorted(CONFIGS.glob("*.json")):
        cfg = json.loads(path.read_text())
        if "engine" not in cfg:
            continue
        for e in cfg["pool"]:
            seen[e["name"]] = (e, cfg["engine"])
    return [seen[k] for k in sorted(seen)]


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("engine", pool(), ids=lambda e: e[0]["name"])
def test_decode_step_compiles_for_v5e(engine, one_chip):
    import jax
    import jax.numpy as jnp
    from harness import boot
    from repro.configs.base import ModelConfig
    from repro.models import model as M
    e, eng = engine
    arch = boot.arch_module(e["arch"])
    cfg = arch.program_config(e["config"], ModelConfig, e["name"])
    slots, cache_len = int(eng["max_slots"]), int(eng["cache_len"])
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = on_chip(jax.eval_shape(
        lambda k: arch.init(k, e["config"], jnp.bfloat16),
        jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(lambda: M.init_caches(cfg, slots,
                                                          cache_len)))
    tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, c, t, q: M.decode_step(p, cfg, c, t, q))
    mem = step.lower(params, caches, tok, pos).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{e['name']}: arguments {mem.argument_size_in_bytes} "
          f"outputs {mem.output_size_in_bytes} temporaries "
          f"{mem.temp_size_in_bytes} aliased {mem.alias_size_in_bytes}")
    assert mem.argument_size_in_bytes >= 2 * arch.param_count(e["config"])
    assert total < 16 * 10 ** 9
