"""The program's spans in traced runs of a routing cell: per-layer
readings, a wave split by stage and the device's idle time put down to
spans; the benchmark's own runs never run this.

    python bench/run.py --workload knn100.exact100k.route8 --seed 1 \
        --seconds 10 --trace 1 --trace-out route8.json.gz
    python bench/spans_report.py route8.json.gz [more.json.gz ...]

Each file holds the events that ``run.py --trace-out`` writes (and that
`harness.trace.events` reads from the profiler's ``.xplane.pb``).  For
each it prints one JSON line (`harness.spans`):

* ``metrics``: ``tokenize_ms``, ``encode_fetch_ms``, ``encoder_device_ms``,
  ``route_host_ms``, ``route_fetch_ms`` and ``route_device_ms``;
* ``stages``: a wave split by span, medians in ms;
* ``waves``: the number of ``route`` spans; ``window_s`` as traced;
* ``idle``: the first device's idle seconds, the share inside a program
  span, and each span's seconds and share.

A trace of a program without spans reads no metric, and all its idle
time ``outside``.
"""
from __future__ import annotations

import argparse
import gzip
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import spans  # noqa: E402


def report(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    sp = spans.reduce(rec["events"])
    return {"file": str(path), "window_s": rec.get("window_s"),
            "waves": len(sp["calls"].get("route", [])),
            "metrics": spans.metrics(sp), "stages": spans.stages(sp),
            "idle": spans.idle_summary(sp)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("traces", nargs="+",
                    help="events written by run.py --trace-out (.json.gz)")
    for path in ap.parse_args(argv).traces:
        print(json.dumps(report(path)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
