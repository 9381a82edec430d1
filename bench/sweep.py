#!/usr/bin/env python3
"""Find a read cell's knee: the highest offered rate it sustains.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --rates 10,20,30

One process loads the cell's store once, then runs one window per rate
(the cell's own mix, a schedule of its own per rate) and prints one JSON
line per rate: the latency percentiles, how far the last answer came after
the window closed, how late waves started in the first and the last
third of the window, and the share of the window spent serving.  Before
each window the process drops its compiled programs and runs the set-up's
shapes again, and the window runs with the persistent cache off, so each
rate meets the gather lengths as a fresh run does.  A rate the cell
sustains ends its window within about one wave and starts its last waves
no later than its first.  Runs on the
chip only, like ``run.py``; the cell's traffic file then takes a fixed rate
below the knee.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def backlog(wl, seconds: float) -> dict:
    import numpy as np
    late = np.asarray(wl.late_s)
    third = max(1, len(late) // 3)
    return {"overrun_s": wl.elapsed_s - seconds,
            "late_first_s": float(late[:third].mean()),
            "late_last_s": float(late[-third:].mean())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import jax
    import harness
    import openloop
    import warm
    cell = harness.find_cell(ROOT, args.workload)
    hooks = harness.Hooks()
    if harness.open_devices(cell, hooks) is None:
        return 2
    compiles = harness.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    ses = harness.Session(cell, args.seed, hooks)
    ses.compile_shapes()
    ses.warm_up()
    harness.log(f"set-up {time.perf_counter() - T_START:.1f} s: {ses.phases}")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        jax.clear_caches()
        ses.compile_shapes()
        traffic = dict(cell.traffic, rate_qps=rate)
        sched = openloop.make_schedule(traffic, args.seconds, args.seed,
                                       stream=10 + i)
        w0 = time.perf_counter()
        with warm.cache_off():
            wl = ses.window(sched, ses.resolver(sched), args.seconds)
        n_compiles, compile_s, _ = compiles.between(w0, time.perf_counter())
        lat = wl.latency_s
        line = {"rate_qps": rate, "queries": len(lat), "waves": len(wl.waves),
                "p50_ms": 1e3 * openloop.percentile(lat, 50),
                "p95_ms": 1e3 * openloop.percentile(lat, 95),
                "serve_s": wl.serve_s, "busy": wl.serve_s / wl.elapsed_s,
                "compiles": n_compiles, "compile_s": compile_s,
                **backlog(wl, args.seconds),
                "commits": len(ses.writer.committed) if ses.writer else 0,
                "correct": not any(harness.compare(ses.ref, wl).values())}
        print(json.dumps(line), flush=True)
    ses.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
