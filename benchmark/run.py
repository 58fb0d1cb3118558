#!/usr/bin/env python3
"""The benchmark's command: one cell, once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name. The cell's entry in ``BENCHMARK.json`` names
its configuration (``configs/<config>.json``) and its traffic mix
(``traffic/<mix>.json``, whose ``kind`` is the generator
``traffic/<kind>.py``); ``workloads/<cell>.json``, where there is one,
overrides numbers of the mix for this cell. The metrics a cell reports are
those of ``BENCHMARK.json`` that list it under ``workloads`` (or list no
cells); a per-layer metric is read by ``layers/<reader>.py``, the part of
its name before the first dot, which may also say what it wants counted
before and after the window (``snapshot``). So a later PR adds a
configuration, a mix, a kind, a cell, or a layer metric on a cell that is
already there, by adding files and entries to ``BENCHMARK.json``.

The last line of standard output is the result. Every earlier line is a
JSON object that names the platform, the device kind and the count.
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


class Ctx:
    """What a traffic kind and a layer reader are handed."""


def metrics_of(bench: dict, group: str, cell: str) -> dict:
    """name -> unit of the metrics of ``group`` that ``cell`` reports."""
    return {
        m["name"]: m["unit"] for m in bench[group]
        if cell in m.get("workloads", [cell])
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--set", action="append", default=[], metavar="KEY=NUMBER",
        help="override one number of the traffic mix (the knee sweep); "
             "the override is printed",
    )
    ap.add_argument(
        "--control", type=int, choices=(0, 1), default=0,
        help="also run the comparison's control (the replay with one "
             "sequenced op withheld) and print what it found; the "
             "benchmark's own runs do not",
    )
    args = ap.parse_args(argv)

    # A run that hangs says where: every thread's stack on stderr, once
    # it has lasted longer than any warm run does.
    faulthandler.dump_traceback_later(300, exit=False)
    from benchmark import harness as H

    bench = H.load_json("..", "BENCHMARK.json")
    listed = {w["name"]: w for w in bench["workloads"]}
    own = ("workloads", f"{args.workload}.json")  # the cell's own numbers
    cell = H.load_json(*own) if os.path.exists(os.path.join(H.BENCH, *own)) else {}
    rehearsal = bool(cell.get("rehearsal")) and args.workload not in listed
    if rehearsal:  # stands for a listed cell, whose metrics it reports
        as_cell = cell["as_cell"]
    elif args.workload in listed:
        as_cell, cell = args.workload, {**cell, **listed[args.workload]}
    else:
        H.fail(f"benchmark: {args.workload}: neither listed in "
               "BENCHMARK.json nor a rehearsal workload")
    end_to_end = metrics_of(bench, "end_to_end", as_cell)
    per_layer = metrics_of(bench, "per_layer", as_cell)
    readers = {
        m: importlib.import_module(f"benchmark.layers.{m.split('.', 1)[0]}")
        for m in per_layer
    }
    ctx = Ctx()
    ctx.out = out = H.Out()
    ctx.cell, ctx.rehearsal, ctx.seed = cell, rehearsal, args.seed
    ctx.config = H.load_json("configs", f"{cell['config']}.json")
    mix = H.load_json("traffic", f"{cell['traffic']}.json")
    ctx.params = {**mix["params"], **cell.get("params", {})}
    for item in args.set:
        key, _, value = item.partition("=")
        if key not in ctx.params:
            H.fail(f"benchmark: --set {key}: the mix has no such number")
        ctx.params[key] = float(value)
    ctx.run_dir = os.path.join(ROOT, "benchmark_out", args.workload)
    os.makedirs(ctx.run_dir, exist_ok=True)

    # One compile cache inside the checkout (or where the environment
    # says), before anything compiles; then the device, once.
    from fluidframework_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    H.find_devices(out, cell["chips"], rehearsal)
    ctx.meter = H.CompileMeter()
    out.say(
        "start", workload=args.workload, config=cell["config"],
        traffic=cell["traffic"], kind=mix["kind"], seed=args.seed,
        seconds=args.seconds, trace=args.trace, rehearsal=rehearsal,
        overrides=args.set, compile_cache_dir=cache_dir,
    )
    kind = importlib.import_module(f"benchmark.traffic.{mix['kind']}")

    state = kind.setup(ctx)
    try:
        ctx.base_pool = H.pool_shape(
            state.srv, state.server_cfg["device_capacity"]
        )
        out.say("fleet", resident_documents=ctx.config["resident_documents"],
                base_pool=list(ctx.base_pool))
        kind.warm(ctx, state)
        tracer = H.Tracer(
            bool(args.trace), os.path.join(ctx.run_dir, "trace"), state.srv,
            readers.values(),
        )
        setup = ctx.meter.snapshot()
        before = H.counters(state.srv, readers.values())
        built = H.aot_keys()
        setup_s = time.time() - T_START
        res = kind.run(ctx, state, args.seconds, tracer)
        after = H.counters(state.srv, readers.values())
        window = ctx.meter.snapshot()
        memory = H.memory_peak_bytes()
        if hasattr(kind, "collect"):  # what arrives only after the window
            res = kind.collect(ctx, state, res)
        ctx.window = H.delta(after, before)
        out.say(
            "window", seconds=res["window_s"], setup_s=setup_s,
            compiles_in_window=window["compiles"] - setup["compiles"],
            aot_builds_in_window=ctx.window["aot_builds"],
            aot_keys_built_in_window=sorted(H.aot_keys() - built),
            migrations_in_window=ctx.window["migrations"],
            setup_compile_s=setup["compile_s"],
            setup_cache_hits=setup["cache_hits"],
            setup_cache_misses=setup["cache_misses"],
            counters=ctx.window, **res["notes"],
        )
        ctx.control = bool(args.control)
        checks = kind.verify(ctx, state)
    finally:
        kind.teardown(ctx, state)

    correct = True
    for name, value, limit in checks:
        ok = limit is None or value <= limit
        correct = correct and ok
        out.say("compared", what=name, value=value, limit=limit, ok=ok)

    values = dict(res["metrics"], setup_s=setup_s)
    line = {
        "correct": correct, "attempted": res["attempted"],
        "failed": res["failed"], "metrics": {},
        "device": {**out.device, "memory_peak_bytes": memory},
    }
    if args.trace:
        from benchmark import trace_reduce

        events = trace_reduce.load_xplane(tracer.dir)
        ctx.trace = trace_reduce.reduce(events)
        ctx.trace["counters"] = H.delta(tracer.after, tracer.before)
        ctx.result = res
        out.say(
            "trace", busy_s=ctx.trace["busy_s"], window_s=ctx.trace["window_s"],
            devices=ctx.trace["devices"], events=len(events),
            op_counts=dict(sorted(
                ctx.trace["op_counts"].items(), key=lambda kv: -kv[1]
            )[:24]),
            counters=ctx.trace["counters"],
        )
        if ctx.trace["busy_s"] <= 0 and not rehearsal:
            H.fail("benchmark: no operation ran on the device in the trace")
        line["device"]["busy_s"] = ctx.trace["busy_s"]
        line["device"]["window_s"] = ctx.trace["window_s"]
        line["breakdown"] = {
            "device_ops": ctx.trace["device_ops"],
            "idle_gaps": ctx.trace["idle_gaps"],
        }
        for metric, unit in per_layer.items():
            value = readers[metric].read(ctx)
            if value is not None:
                line["metrics"][metric] = {"value": value, "unit": unit}
    else:
        for metric, unit in end_to_end.items():
            line["metrics"][metric] = {"value": values[metric], "unit": unit}
    faulthandler.cancel_dump_traceback_later()
    out.result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
