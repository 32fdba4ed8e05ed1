"""Readings that set the correctness limits, and the knee of a mix, on the
chip; the benchmark's own runs never run this.

    python bench/control.py --workload <cell> --seconds S --seeds 1 2 3 ...
    python bench/control.py --workload <config>:<mix> ...   # a cell held back
    python bench/control.py --workload <cell> --seconds S --seeds 1 \
        --rates 1.0 2.0 3.0        # open-loop rate sweep

One process boots the cell as `run.py` does and then, for each seed, runs
the cell's traffic for ``S`` seconds through the timed path (the gateway,
or the routing hop alone) and reads, for what it answered, with
`run.correctness` and `run.judge` (what a run reads and decides):

* ``program``: the compared numbers, and whether the run is correct;
* ``control.<mode>``: the same, with the reference one precision step down
  put in the program's place: fp8 (e4m3) weights with bf16 activations for
  the engines (they state bf16), and the router's reference in ``mode``
  (`route_ref.MODES`, ``--controls``);
* ``witness`` (``--witness``, the gateway's cells): the gap of the tokens
  that the program's own full-sequence forward
  (`repro.models.model.forward`) puts first at the same positions.

With ``--rates`` it instead reports, for each offered rate, what completed,
what was shed and how the latencies moved from the first to the last third
of the window.  One JSON line per seed or rate; the last line sums up.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from harness import boot, check, spec, stats  # noqa: E402


def serve_window(gw, model, mix_path, seed, seconds, rate=None):
    child = run.start_loadgen(gw.port, model, mix_path, seed, seconds, rate)
    child.stdin.write("go\n")
    child.stdin.flush()
    out, _ = child.communicate(timeout=seconds + run.GRACE_S + 120)
    return json.loads(out.strip().splitlines()[-1])


def wait_idle(gw, timeout_s: float = 180.0) -> bool:
    """Until the gateway holds no request: a sweep point starts empty."""
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        with gw._lock:
            if not gw._pending and gw.batcher.pending() == 0:
                return True
        time.sleep(0.1)
    return False


def witness_gaps(cfg, params, arch, hf, recs):
    """Gaps under the reference of the tokens the program's full-sequence
    forward puts first at each served position."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as M
    toks, served, where = check.sequences(recs, int(hf["vocab_size"]))
    fwd = jax.jit(lambda p, t: M.forward(p, cfg, {"tokens": t})[0])
    ref = jax.jit(lambda p, t: arch.forward(p, hf, t, "reference"))
    lw = np.asarray(fwd(params, jnp.asarray(toks)))
    lr = np.asarray(ref(params, jnp.asarray(toks)))
    out = []
    for j, w in enumerate(where):
        pick = lw[j, w].argmax(-1)
        rows = lr[j, w]
        out.append(rows.max(-1) - rows[np.arange(len(w)), pick])
    return np.concatenate(out)


def route_windows(cell, seeds, seconds):
    """The routing hop booted once, then one window per seed."""
    from harness import routing, traffic
    wave = int(cell.traffic["wave"])
    t0 = time.perf_counter()
    svc, timings = routing.start(cell.config, run.CACHE, wave)
    run.say("booted", seconds=time.perf_counter() - t0, **timings)
    loads = {seed: routing.serve(svc, traffic.schedule(
        cell.traffic, seed, seconds), wave, seconds) for seed in seeds}
    del svc
    run.free_device()
    return loads


def sweep(gw, model, mix_path, seed, seconds, rates):
    """One window per offered rate: what completed, what was shed, and
    how the latencies moved from the first to the last third."""
    for rate in rates:
        wait_idle(gw)
        load = serve_window(gw, model, mix_path, seed, seconds, rate)
        recs = load["records"]
        third = seconds / 3
        first = [r for r in recs if r["due"] - load["t0"] < third]
        last = [r for r in recs if r["due"] - load["t0"] >= 2 * third]
        tt = lambda rs, q: stats.percentile(stats.ttfts(rs), q)  # noqa
        print(json.dumps({
            "rate": rate, "attempted": len(recs),
            "ok": sum(stats.ok(r) for r in recs),
            "shed": sum(r["status"] == "shed" for r in recs),
            "failed": sum(r["status"] == "failed" for r in recs),
            "unfinished": load["unfinished"],
            "ttft_p50_first_third": tt(first, 50),
            "ttft_p50_last_third": tt(last, 50),
            "ttft_p90": tt(recs, 90), "ttft_p50": tt(recs, 50),
            "last_end_after_close_s": max(
                (r.get("end", 0) for r in recs), default=0)
            - load["t0"] - seconds,
            "tokens_per_s": stats.tokens_in_window(
                recs, load["t0"], seconds) / seconds,
            "late_max_s": load["late_max_s"]}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json, or CONFIG:TRAFFIC for "
                         "a pair that it does not list")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="*", default=None)
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--controls", nargs="*", default=["bfloat16"],
                    help="precisions of the router's control "
                         "(route_ref.MODES); the engines' is fp8")
    ap.add_argument("--bench-json", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--data-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--no-chip", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    data = Path(args.data_dir) if args.data_dir else BENCH
    cell = (spec.make_cell(*args.workload.split(":"), data)
            if ":" in args.workload else spec.load_cell(
                args.workload, args.bench_json and Path(args.bench_json),
                data))
    if not args.no_chip:
        run.require_chip(cell.chips)
    run.compile_cache()
    cfg = cell.config
    route = cell.traffic.get("endpoint", "chat") == "route"
    program_cfgs = {}
    if route:
        loads = route_windows(cell, args.seeds, args.seconds)
    else:
        t0 = time.perf_counter()
        engines, gw, model, timings = boot.start(cfg, run.CACHE)
        run.say("booted", seconds=time.perf_counter() - t0, **timings)
        mix_path = data / "traffic" / f"{cell.traffic_name}.json"
        if args.rates:
            sweep(gw, model, mix_path, args.seeds[0], args.seconds,
                  args.rates)
            gw.close()
            return 0
        program_cfgs = {n: eng.cfg for n, eng in engines.items()}
        loads = {seed: serve_window(gw, model, mix_path, seed, args.seconds)
                 for seed in args.seeds}
        gw.close()
        del gw, engines
        run.free_device()

    rows = []
    for seed in args.seeds:
        recs = loads[seed]["records"]
        left = loads[seed].get("unfinished", 0)
        row = {"seed": seed, "attempted": len(recs),
               "ok": sum(stats.ok(x) for x in recs), "served": {}}
        for x in recs:
            if stats.ok(x):
                row["served"][x["served_by"]] = (
                    row["served"].get(x["served_by"], 0) + 1)
        row["program"] = run.correctness(cell, recs, seed)
        row["program_correct"] = run.judge(cell, row["program"], recs,
                                           left)[0]
        for mode in args.controls:
            row[f"control.{mode}"] = run.correctness(cell, recs, seed,
                                                     control=mode)
            row[f"control_correct.{mode}"] = run.judge(
                cell, row[f"control.{mode}"], recs, left)[0]
        rows.append(row)
    if args.witness and program_cfgs:
        for e in cfg["pool"]:
            arch = boot.arch_module(e["arch"])
            params = boot.make_weights(e)
            for seed, row in zip(args.seeds, rows):
                er = [x for x in check.sample(
                    loads[seed]["records"], seed,
                    int(cell.traffic["check_tokens"]))
                    if x["served_by"] == e["name"]]
                if er:
                    row.setdefault("witness", {})[
                        f"logit_gap.{e['name']}"] = float(witness_gaps(
                            program_cfgs[e["name"]], params, arch,
                            e["config"], er).max())
            del params
            run.free_device()
    for row in rows:
        print(json.dumps(row), flush=True)
    summary = {}
    for side in sorted({k for r in rows for k in r
                        if k == "program" or k.startswith("control.")
                        or k == "witness"}):
        keys = sorted({k for r in rows for k in r.get(side, {})})
        summary[side] = {k: [r.get(side, {}).get(k) for r in rows]
                         for k in keys}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
