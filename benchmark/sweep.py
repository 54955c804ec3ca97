"""Find the knee of an open-loop cell once, when the cell is defined:

    python -m benchmark.sweep --workload <cell> --seed <n> --seconds <s> --rates 8,12,16

One process: the system is built and warmed as ``benchmark.run`` does
it, then each rate is offered for ``--seconds`` (after the workload's
``lead_s``) on the same sessions, lowest first.  A rate is sustained
if nothing failed and the completions in the second half of its
window are at least 0.97 of those offered there.  Prints one line per
rate and a last line with the highest sustained rate; the rate written
into the workload file is four fifths of it, as a whole number.  Not
part of any check: later PRs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from benchmark import run
from benchmark.traffic import percentile


def sweep(bench, arguments) -> dict:
    bench.warm_up()
    rows, best = [], None
    for rate in [float(r) for r in arguments.rates.split(",")]:
        bench.traffic.spec["rate"] = rate
        outcome = bench.window(float(arguments.seconds), False)
        numbers = outcome["numbers"]
        half = outcome["start_s"] + outcome["seconds"] / 2.0
        end = outcome["start_s"] + outcome["seconds"]
        offered = [r for r in numbers["requests"]
                   if r["due_s"] >= half]
        completed = [r for r in outcome["records"]
                     if r["status"] == "ok" and half <= r["recv_s"] < end]
        share = len(completed) / max(1, len(offered))
        latencies = numbers["latencies_ms"]
        sustained = numbers["failed"] == 0 and share >= 0.97
        row = {"rate": rate, "offered": numbers["attempted"],
               "failed": numbers["failed"],
               "second_half_completed_over_offered": share,
               "latency_p50_ms": percentile(latencies, 50)
               if latencies else None,
               "latency_p95_ms": percentile(latencies, 95)
               if latencies else None,
               "tokens_per_s": numbers["tokens_per_s"],
               "rows_per_step":
                   outcome["counters"]["batcher.tokens_emitted"]
                   / max(1, outcome["counters"]["batcher.steps"]),
               "window_builds": outcome["window_builds"],
               "sustained": sustained}
        if bench.rehearse:      # counts only: no CPU number under a
            row = {key: row[key] for key in (       # metric's name
                "rate", "offered", "failed", "window_builds",
                "second_half_completed_over_offered", "sustained")}
        print(json.dumps(row), flush=True)
        rows.append(row)
        if sustained:
            best = rate
    return {"sweep": rows, "highest_sustained": best,
            "four_fifths": None if best is None else int(best * 0.8),
            "device": bench.device}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--rehearse", action="store_true")
    arguments = parser.parse_args(argv)
    bench = run.prepare(arguments)
    rates = len(arguments.rates.split(","))
    limit_s = sum(bench.limits.values()) + 600.0 + rates * (
        arguments.seconds + bench.limits["drain"] + 30.0)
    try:
        result = run.in_thread(bench, lambda: sweep(bench, arguments),
                               limit_s)
    except run.Expired as error:
        bench.dump(str(error))
        sys.stderr.flush()
        os._exit(3)
    print(json.dumps(result), flush=True)
    sys.stderr.flush()
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
