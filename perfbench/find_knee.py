#!/usr/bin/env python3
"""Find the knee of an open-loop cell ONCE, by hand, on the chip:

    python3 perfbench/find_knee.py --workload m7b-1chip.chat-poisson \
        --rates 0.5,0.7,0.9,1.1 --seconds 40 --seed 0

One process and one server: for each rate, the cell's traffic (its own
warm-up included) at that rate, then a drain. Prints one JSON line per rate:
requests due, completed, shed, the backlog (queued + in flight) when the
window opened and when it closed, time to first token (median, 90th
percentile) and the generator's lateness. The knee is the highest rate with
no shed request and no backlog that grows through the window; the cell runs
at about four fifths of it (``perfbench/cells/<cell>.json``), and PERF.md
keeps the table. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--param", default="rate_rps")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from perfbench import harness, loadgen, metric_math
    cell = harness.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("only an open-loop cell has a knee to find")
    import jax
    if jax.devices()[0].platform != "tpu":
        print("find_knee.py: no TPU", file=sys.stderr)
        return 4
    adapter = harness.load_module(os.path.join(
        "perfbench", "adapters", cell.config["adapter"] + ".py"))
    adapter.enable_cache()
    server = adapter.Server(cell.config, cell.chips, args.seed)
    clock = time.perf_counter
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = loadgen.Traffic(cell.traffic, {**cell.params,
                                                 args.param: rate},
                                  server.vocab_size, args.seed + i,
                                  args.seconds)
        spans = harness.Spans(clock)
        records, window = harness.serve_open(
            server, traffic, spans, harness.Tracer(None, 0.0), clock)
        c = server.counters()
        mine = [r for r in records if window[0] <= r.origin < window[1]]
        # sent before the window opened and not done by then
        backlog_open = sum(
            1 for r in records if r.sent < window[0]
            and not (r.complete and r.token_times[-1] < window[0]))
        ttft = [r.first - r.origin for r in mine]
        late = [r.sent - r.due for r in mine]
        steps = spans.durations("bench.sched_step", window)
        print(json.dumps({
            "rate_rps": rate, "due": len(mine),
            "completed": sum(r.complete for r in mine),
            "first_token": sum(1 for r in mine if r.token_times),
            "shed_total": c["requests_shed_total"],
            "backlog_open": backlog_open,
            "backlog_close": c["queue_depth"] + c["inflight"],
            "queued_close": c["queue_depth"],
            "ttft_p50_s": metric_math.percentile(ttft, 50),
            "ttft_p90_s": metric_math.percentile(ttft, 90),
            "late_p90_s": metric_math.percentile(late, 90),
            "step_p50_s": metric_math.median(steps) if steps else None,
        }), flush=True)
        while server.busy():            # drain before the next rate
            server.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
