"""The benchmark: one cell of ``BENCHMARK.json`` per run, on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The process that holds the chip builds the routed gateway from the
program's parts (`harness.boot`), warms up every shape the cell's traffic
uses, and then measures for ``--seconds`` while a child process that never
imports JAX offers the cell's traffic (`harness.loadgen`).  ``--seed``
drives the traffic only; weights, support set and index are fixed by the
configuration.  With ``--trace 1`` the window is traced and the per-layer
metrics are reported instead of the end-to-end ones.

After the window the program's state is freed and its answers are compared
with the plain references (`harness.check`).  The last lines of standard
error give each compared number beside its limit; the last line of
standard output is the result, a JSON object.  Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import boot, check, spec, stats  # noqa: E402
from harness import peaks as peaks_mod  # noqa: E402
from harness import trace as trace_mod  # noqa: E402

#: seconds past the window's close that requests still in flight may take
GRACE_S = 60.0
CACHE = BENCH / ".cache"


class Run:
    """What the metric readers see of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def say(label: str, **fields) -> None:
    print(f"[bench] {label}: " + json.dumps(fields, sort_keys=True),
          flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--bench-json", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--data-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--trace-out", default=None,
                    help="also write the trace's events here (.json.gz)")
    ap.add_argument("--waves-out", default=None,
                    help="routing cells: also write each wave's times here")
    return ap.parse_args(argv)


def require_chip(chips: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX finds no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}; nothing was run")


def compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else a fixed directory inside the checkout.  Every program
    is cached, however quick its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def engine_stats(engines) -> dict:
    return {n: dict(e.stats) for n, e in engines.items()}


def start_loadgen(port: int, model: str, mix_path: Path, seed: int,
                  seconds: float, rate=None) -> subprocess.Popen:
    """The load generator's process, its schedule built, waiting for the
    line that starts the window."""
    child = subprocess.Popen(
        [sys.executable, str(BENCH / "harness" / "loadgen.py"),
         "--port", str(port), "--model", model, "--mix", str(mix_path),
         "--seed", str(seed), "--seconds", str(seconds),
         "--grace", str(GRACE_S)]
        + ([] if rate is None else ["--rate", str(rate)]),
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    line = child.stdout.readline()
    if line.strip() != "ready":
        child.kill()
        child.wait()
        raise RuntimeError(f"load generator did not start: {line!r}")
    return child


def free_device() -> None:
    """Delete every array on the device (the program's state: weights,
    caches, index), so that the reference runs alone there."""
    import jax
    from repro.serving import encoder
    encoder._encoder.cache_clear()
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()


def correctness(cell, records, seed: int, control: str = None) -> dict:
    """The compared numbers of this run (`harness.check`).  With
    ``control`` (a `route_ref.MODES` precision) the control's answers
    stand in the program's place: the engines' fp8 forward and the router
    computed in that precision, on the same requests."""
    from harness import route_ref
    cfg = cell.config
    numbers = {}
    if "check_tokens" in cell.traffic:
        smp = check.sample(records, seed, int(cell.traffic["check_tokens"]))
        for e in cfg["pool"]:
            recs = [r for r in smp if r["served_by"] == e["name"]]
            if not recs:
                continue
            params = boot.make_weights(e)
            gaps = check.engine_gaps(boot.arch_module(e["arch"]), e["config"],
                                     params, recs, control=bool(control))
            numbers[f"logit_gap.{e['name']}"] = float(gaps.max())
            say(f"checked {e['name']}", requests=len(recs), tokens=len(gaps))
            del params
            free_device()
    if "check_requests" in cell.traffic:
        recs = check.sample_routed(records, seed,
                                   int(cell.traffic["check_requests"]))
    else:
        recs = [r for r in records if stats.ok(r)]
    r = cfg["router"]
    names = [e["name"] for e in cfg["pool"]]
    ref_dir = CACHE / "reference" / cfg["name"]
    router = route_ref.Router(boot.support_texts(cfg), names, int(r["seed"]),
                              int(r["k"]), cache_dir=ref_dir)
    if recs:
        ref = router.answers([x["text"] for x in recs],
                             [x["lam"] for x in recs])
        ans = (check.served_answers(router, recs) if control is None else
               check.control_answers(route_ref.Router(
                   boot.support_texts(cfg), names, int(r["seed"]),
                   int(r["k"]), mode=control, cache_dir=ref_dir), recs))
        numbers.update(check.route_numbers(ref, recs, ans))
    say("checked routing", requests=len(recs))
    return numbers


def expected(cell, records) -> list:
    """The numbers a run of ``cell`` compares: the routing answers, and for
    the gateway's cells each engine that served a request."""
    if cell.traffic.get("endpoint", "chat") == "route":
        return ["route_regret_mean", "score_gap_mean", "embed_gap"]
    served = {r["served_by"] for r in records if stats.ok(r) and r["tokens"]}
    return ["route_regret_mean"] + [f"logit_gap.{e['name']}"
                               for e in cell.config["pool"]
                               if e["name"] in served]


def judge(cell, numbers, records, unfinished) -> tuple:
    """(correct, the checks as printed): each number the cell compares
    against its configured limit (a number or a limit that is missing
    fails), and no request failed or left unfinished."""
    limits = cell.config["limits"]
    checks = check.verdict(numbers, {k: limits.get(k)
                                     for k in expected(cell, records)})
    broken = sum(r["status"] == "failed" for r in records)
    checks = {**{k: {"value": c["value"], "limit": c["limit"],
                     "ok": c["ok"]} for k, c in checks.items()},
              "failed_requests": {"value": broken, "limit": 0,
                                  "ok": broken == 0},
              "unfinished_requests": {"value": unfinished, "limit": 0,
                                      "ok": unfinished == 0}}
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return (all(c["ok"] for c in checks.values()),
            {k: {"value": c["value"], "limit": c["limit"]}
             for k, c in checks.items()})


def serve_chat(cell, args, data_dir: Path, devs) -> dict:
    """The gateway path: boot, the load generator's window, and what the
    metric readers see.  The program's state is freed on return."""
    cfg = cell.config
    engines, gw, model, timings = boot.start(cfg, CACHE)
    setup_s = time.perf_counter() - T_START
    say("set-up", setup_s=setup_s, **timings)

    compiles = boot.count_compiles()
    mix_path = data_dir / "traffic" / f"{cell.traffic_name}.json"
    child = start_loadgen(gw.port, model, mix_path, args.seed, args.seconds)
    before = engine_stats(engines)
    cap = start_trace() if args.trace else None
    window_compiles = dict(compiles)
    child.stdin.write("go\n")
    child.stdin.flush()
    t_go = time.perf_counter()
    time.sleep(max(0.0, t_go + args.seconds - time.perf_counter()))
    after = engine_stats(engines)
    window_compiles = {k: compiles[k] - window_compiles.get(k, 0)
                       for k in ("compiles", "lookups", "hits")}
    trace = stop_trace(cap, args, len(devs))
    out, _ = child.communicate(timeout=GRACE_S + 120)
    load = json.loads(out.strip().splitlines()[-1])
    gw.close()
    memory_peak = peak_bytes(devs)

    records = load["records"]
    served = {}
    for r in records:
        if stats.ok(r):
            served[r["served_by"]] = served.get(r["served_by"], 0) + 1
    say("window", requests=len(records), served=served,
        unfinished=load["unfinished"], compiles_in_window=window_compiles,
        generator_late_max_s=load["late_max_s"],
        generator_late_mean_s=load["late_mean_s"])
    eng = []
    for e in cfg["pool"]:
        arch = boot.arch_module(e["arch"])
        delta = {k: after[e["name"]][k] - before[e["name"]][k]
                 for k in after[e["name"]]}
        eng.append({"name": e["name"], "delta": delta,
                    "max_slots": int(cfg["engine"]["max_slots"]),
                    "flops_per_token": arch.flops_per_token(
                        e["config"], cfg["engine"]["cache_len"] / 2)})
        say(f"engine {e['name']}", **delta)
    del gw, engines
    free_device()
    return {"records": records, "t0": load["t0"], "engines": eng,
            "trace": trace, "setup_s": setup_s, "timings": timings,
            "memory_peak": memory_peak, "unfinished": load["unfinished"],
            "compiles_in_window": window_compiles["compiles"]}


def serve_route(cell, args, devs) -> dict:
    """The routing hop alone (`harness.routing`): boot, waves of the mix
    back to back for the window, and what the metric readers see.  The
    program's state is freed on return."""
    from harness import routing, traffic
    mix = cell.traffic
    wave = int(mix["wave"])
    svc, timings = routing.start(cell.config, CACHE, wave)
    reqs = traffic.schedule(mix, args.seed, args.seconds)
    setup_s = time.perf_counter() - T_START
    say("set-up", setup_s=setup_s, **timings)
    compiles = boot.count_compiles()
    cap = start_trace() if args.trace else None
    load = routing.serve(svc, reqs, wave, args.seconds)
    trace = stop_trace(cap, args, len(devs))
    memory_peak = peak_bytes(devs)
    waves = load["waves"]
    if args.waves_out:
        with open(args.waves_out, "w") as f:
            json.dump({"t0": load["t0"], "waves": waves}, f)
    served = {}
    for r in load["records"]:
        served[r["served_by"]] = served.get(r["served_by"], 0) + 1
    say("window", waves=len(waves), routed=len(load["records"]),
        served=served, wraps=load["wraps"],
        compiles_in_window=compiles["compiles"],
        encode_s=sum(w["encode_s"] for w in waves),
        route_s=sum(w["route_s"] for w in waves))
    del svc
    free_device()
    return {"records": load["records"], "t0": load["t0"],
            "t_end": load["t_end"], "waves": waves,
            "engines": [], "trace": trace, "setup_s": setup_s,
            "timings": timings, "memory_peak": memory_peak, "unfinished": 0,
            "compiles_in_window": compiles["compiles"]}


def start_trace():
    cap = trace_mod.Capture(tempfile.mkdtemp(prefix="bench-trace-"))
    cap.start()
    return cap


def stop_trace(cap, args, n_devices: int):
    """The traced window reduced (`harness.trace`), or None untraced."""
    if cap is None:
        return None
    path = cap.stop()
    evs = trace_mod.events(path)
    trace = trace_mod.reduce(evs, cap.seconds, n_devices=n_devices)
    if args.trace_out:
        import gzip
        with gzip.open(args.trace_out, "wt") as f:
            json.dump({"window_s": cap.seconds, "events": evs}, f)
    shutil.rmtree(cap.log_dir, ignore_errors=True)
    return trace


def peak_bytes(devs) -> int:
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs))


def main(argv=None, chip: bool = True, peaks: dict = None) -> int:
    args = parse(argv)
    data_dir = Path(args.data_dir) if args.data_dir else BENCH
    cell = spec.load_cell(args.workload, Path(args.bench_json)
                          if args.bench_json else None, data_dir)
    if chip:
        require_chip(cell.chips)
    import jax
    say("compile cache", dir=compile_cache())
    devs = jax.devices()[:cell.chips]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say("device", **device)
    pk = peaks or peaks_mod.peaks_for(device["kind"])

    if cell.traffic.get("endpoint", "chat") == "route":
        got = serve_route(cell, args, devs)
    else:
        got = serve_chat(cell, args, data_dir, devs)
    records = got["records"]
    run = Run(records=records, t0=got["t0"], t_end=got.get("t_end"),
              seconds=args.seconds,
              missing_s=stats.missing_limit_s(args.seconds, GRACE_S),
              engines=got["engines"], waves=got.get("waves"), peaks=pk,
              config=cell.config,
              chips=len(devs), trace=got["trace"], setup_s=got["setup_s"])
    metrics = spec.report(cell.per_layer if args.trace else cell.end_to_end,
                          run)

    numbers = correctness(cell, records, args.seed)
    correct, checks = judge(cell, numbers, records, got["unfinished"])
    if got["compiles_in_window"]:
        say("compiled inside the window", count=got["compiles_in_window"])
    device["memory_peak_bytes"] = got["memory_peak"]
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": sum(not stats.ok(r) for r in records),
              "metrics": metrics, "device": device}
    if got["trace"] is not None:
        device["busy_s"] = got["trace"]["busy_s"]
        device["window_s"] = got["trace"]["window_s"]
        result["breakdown"] = {"device_ops": got["trace"]["device_ops"],
                               "idle_gaps": got["trace"]["idle_gaps"]}
    # a configuration's first run on a checkout fits its router: its
    # set-up is not that of the runs after it
    result["setup"] = {"cold": "fit_s" in got["timings"], **got["timings"]}
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
