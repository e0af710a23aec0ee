#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: loads, warms up every shape the cell's traffic uses (set-up),
measures for ``--seconds``, checks the outputs against the plain reference,
prints. The LAST line of stdout is one JSON object with the keys ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``) and, last, ``compared``: each number that ``correct`` held
against a limit, beside that limit; everything else goes on earlier lines or into
``perfbench_out/<cell>/`` inside the checkout. ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits with a
non-zero code and prints no result line. ``--rehearse`` (with
``JAX_PLATFORMS=cpu`` asked for explicitly) runs the CONTROL FLOW at the tiny
sizes of ``perfbench/testdata/rehearsal/<cell>.json`` on the CPU; its last
line names the CPU as its device and carries no metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse                     # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

EXIT_NO_DEVICE = 4
TRACE_SECONDS = 6.0


def say(**facts) -> None:
    print(json.dumps(facts), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    if args.rehearse and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("run.py: --rehearse is the CPU's control-flow check; ask for "
              "it with JAX_PLATFORMS=cpu", file=sys.stderr)
        return EXIT_NO_DEVICE
    from perfbench import checks, harness, loadgen, metric_math
    cell = harness.load_cell(args.workload, rehearse=args.rehearse)

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.rehearse:
        print(f"run.py: no TPU (JAX found {device}); a CPU number is never "
              "written under a device metric's name. The control flow alone: "
              "JAX_PLATFORMS=cpu ... --rehearse", file=sys.stderr)
        return EXIT_NO_DEVICE
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chip(s), JAX found "
              f"{len(devices)}", file=sys.stderr)
        return EXIT_NO_DEVICE
    peaks = metric_math.device_peaks(device["kind"]) if on_chip else None
    t_device = time.perf_counter() - T_START

    out_dir = os.path.join(ROOT, "perfbench_out", cell.name,
                           f"seed{args.seed}-trace{args.trace}")
    os.makedirs(out_dir, exist_ok=True)
    adapter = harness.load_module(os.path.join(
        "perfbench", "adapters", cell.config["adapter"] + ".py"))
    loop = cell.traffic["loop"]
    if loop not in adapter.LOOPS:
        raise SystemExit(f"adapter {cell.config['adapter']} has no "
                         f"{loop!r} loop (it has {adapter.LOOPS})")
    cache_dir = adapter.enable_cache()
    say(cell=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, device=device, compile_cache=cache_dir,
        cache_entries=len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0)

    clock = time.perf_counter
    spans = harness.Spans(clock)
    tracer = harness.Tracer(
        os.path.join(out_dir, "trace") if args.trace else None,
        min(TRACE_SECONDS, args.seconds / 2))

    requests, steps, tokens_per_step = [], [], 0
    if loop == "train":
        trainer = adapter.Trainer(cell.config, cell.chips, args.seed,
                                  int(cell.traffic["seq_len"]))
        tokens_per_step = trainer.batch * trainer.seq_len
        say(phase="loaded", s=clock() - T_START, reach_device_s=t_device,
            **trainer.load_seconds)
        steps, window = harness.train(trainer, cell.traffic, args.seconds,
                                      args.seed, spans, tracer, clock)
        counters = {}
    else:
        server = adapter.Server(cell.config, cell.chips, args.seed)
        say(phase="loaded", s=clock() - T_START, reach_device_s=t_device,
            **server.load_seconds)
        traffic = loadgen.Traffic(cell.traffic, cell.params,
                                  server.vocab_size, args.seed, args.seconds)
        serve = harness.serve_open if loop == "open" else harness.serve_batch
        requests, window = serve(server, traffic, spans, tracer, clock)
        counters = server.counters()
    trace = tracer.finish()
    if loop == "batch" and tracer.overran:
        # the trace was still running at the batch's last completion: what
        # it reads includes the drain, or nothing but the drain
        say(trace_overran=True)
    device["memory_peak_bytes"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices[:cell.chips])       # before the reference's room
    obs = harness.Observations(
        cell=cell, window=window, setup_s=window[0] - T_START,
        requests=requests, steps=steps, tokens_per_step=tokens_per_step,
        spans=spans, counters=counters, device=device, peaks=peaks,
        trace=trace)

    if loop == "train":
        correct, facts = checks.check_training(trainer, steps, cell.config,
                                               args.seed, on_chip)
        attempted, failed = len(steps), 0
    else:
        correct, facts = checks.check_serving(server, requests, cell.config,
                                              on_chip)
        attempted = len(requests)
        failed = sum(1 for r in requests
                     if r.outcome not in (None, "ok"))
        correct = correct and failed == 0
    compared = facts.pop("compared", {})
    say(phase="checked", correct=correct, s=clock() - T_START, **facts)
    say(counters=counters, spans={
        k: {"n": len(v), "total_s": sum(b - a for a, b in v)}
        for k, v in spans.records.items()})
    with open(os.path.join(out_dir, "records.json"), "w") as f:
        json.dump({
            "window": window, "setup_s": obs.setup_s,
            "requests": [{
                "index": r.index, "n_prompt": r.n_prompt, "n_out": r.n_out,
                "due": r.due, "sent": r.sent, "outcome": r.outcome,
                "tokens": len(r.token_times),
                "first": r.token_times[0] if r.token_times else None,
                "last": r.token_times[-1] if r.token_times else None}
                for r in requests],
            "steps": [[s.done, s.loss] for s in steps]}, f)

    if args.trace:
        metrics = harness.read_metrics(obs, cell.per_layer, "layer_metrics")
    else:
        metrics = harness.read_metrics(obs, cell.end_to_end, "e2e_metrics")
    if args.rehearse:
        say(rehearsal_only={k: v["value"] for k, v in metrics.items()},
            note="CPU control-flow check; these are not measurements")
        metrics = {}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace and trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["breakdown"] = {
            "device_ops": trace.top_ops(10),
            "idle_gaps": [[k, v] for k, v in trace.idle_gaps[:10]]}
    elif args.trace and not args.rehearse:
        print("run.py: the traced window holds no device operation",
              file=sys.stderr)
        return 1
    # each number the comparison held against a limit, beside that limit:
    # last in the result line, and the last lines of standard error
    result["compared"] = {k: {"value": v, "limit": limit}
                          for k, (v, limit) in compared.items()}
    for k, (v, limit) in compared.items():
        print(f"run.py: compared {k} = {v!r} (limit {limit!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
