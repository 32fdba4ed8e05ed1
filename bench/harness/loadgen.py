"""The load generator: a child process that never imports JAX, so that it
shares no interpreter lock with the gateway's threads.

    python loadgen.py --port P --model NAME --mix MIX.json --seed S --seconds T

It builds the run's schedule (`traffic.schedule`), prints ``ready``, waits
for a line on its standard input, and then sends each request when it is
due (open loop) or as soon as its client is free (closed loop), streaming
every completion through ``POST /v1/chat/completions``.  Requests are due
only inside the window of ``T`` seconds; those in flight at its close are
awaited for up to ``--grace`` seconds more.  Every time is
``time.perf_counter()``, which on Linux is the system's monotonic clock, so
the parent reads the same clock.  The last line of standard output is one
JSON object: the window's start ``t0`` and one record per request sent.
"""
from __future__ import annotations

import argparse
import http.client
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import traffic  # noqa: E402


def stream(port: int, model: str, req: dict, timeout_s: float) -> dict:
    """One streamed completion.  Fills ``sent``, ``chunks`` (arrival time
    of each content chunk), ``tokens`` (the token ids they carry),
    ``status`` (``ok``, ``shed`` for a 429, or ``failed``), ``served_by``,
    ``final`` (the gateway's closing payload) and ``end``."""
    rec = dict(req, sent=time.perf_counter(), chunks=[], tokens=[],
               status="failed", served_by=None, final=None, error=None)
    body = json.dumps({"model": f"{model}@lam={req['lam']:g}",
                       "stream": True, "max_tokens": req["max_tokens"],
                       "messages": [{"role": "user",
                                     "content": req["text"]}]})
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    try:
        c.request("POST", "/v1/chat/completions", body=body,
                  headers={"Content-Type": "application/json"})
        r = c.getresponse()
        if r.status != 200:
            rec["status"] = "shed" if r.status == 429 else "failed"
            rec["error"] = f"HTTP {r.status}: {r.read()[:200]!r}"
            return rec
        rec["served_by"] = r.getheader("X-Repro-Served-By")
        done = False
        while True:
            line = r.readline()
            if not line:
                break
            now = time.perf_counter()
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[6:]
            if data == b"[DONE]":
                done = True
                break
            obj = json.loads(data)
            if "error" in obj:
                rec["error"] = json.dumps(obj["error"])[:300]
                continue
            choice = obj["choices"][0]
            content = choice["delta"].get("content", "")
            if content.strip():
                rec["chunks"].append(now)
                rec["tokens"].extend(int(t) for t in content.split())
            if "repro" in obj:
                rec["final"] = obj["repro"]
        if done and rec["error"] is None and rec["final"] is not None:
            rec["status"] = "ok"
    except (OSError, http.client.HTTPException, ValueError) as exc:
        rec["error"] = f"{type(exc).__name__}: {exc}"
    finally:
        c.close()
        rec["end"] = time.perf_counter()
    return rec


def run(port: int, model: str, mix: dict, seed: int, seconds: float,
        grace_s: float, start=None) -> dict:
    reqs = traffic.schedule(mix, seed, seconds)
    print("ready", flush=True)
    if start is not None:
        start()
    t0 = time.perf_counter()
    close = t0 + seconds
    records, lock = [], threading.Lock()
    timeout_s = seconds + grace_s

    def one(req):
        rec = stream(port, model, req, timeout_s)
        with lock:
            records.append(rec)

    threads = []
    if mix["loop"] == "open":
        for req in reqs:
            req["due"] = t0 + req["due"]
            if req["due"] >= close:
                break
            delay = req["due"] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t = threading.Thread(target=one, args=(req,), daemon=True)
            t.start()
            threads.append(t)
    else:
        nxt = iter(reqs)
        take = threading.Lock()

        def client():
            while True:
                now = time.perf_counter()
                if now >= close:
                    return
                with take:
                    req = next(nxt, None)
                if req is None:
                    return
                req["due"] = now
                one(req)

        for _ in range(int(mix["clients"])):
            t = threading.Thread(target=client, daemon=True)
            t.start()
            threads.append(t)
    deadline = close + grace_s
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    with lock:
        out = sorted(records, key=lambda r: r["i"])
    unfinished = sum(t.is_alive() for t in threads)
    late = [r["sent"] - r["due"] for r in out]
    return {"t0": t0, "seconds": seconds, "records": out,
            "unfinished": unfinished,
            "late_max_s": max(late, default=0.0),
            "late_mean_s": sum(late) / len(late) if late else 0.0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--model", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--grace", type=float, default=60.0)
    ap.add_argument("--rate", type=float, default=None,
                    help="override the mix's arrival rate (rate sweeps)")
    args = ap.parse_args(argv)
    with open(args.mix) as f:
        mix = json.load(f)
    if args.rate is not None:
        mix["arrivals"]["rate_per_s"] = args.rate
    out = run(args.port, args.model, mix, args.seed, args.seconds,
              args.grace, start=sys.stdin.readline)
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
