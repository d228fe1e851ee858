"""Where a cell's denoise steps spend device time, by program scope and
program span.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  It builds and warms the cell's engine as ``bench/run.py``
does, serves one window of requests with the profiler off and one with
it on, and prints one JSON line last: ``step_s`` of each window (their
ratio is what tracing costs), the first device's self time by scope
(``device_scopes``) and idle time by program span (``idle_by_span``),
the per-step readings of ``benchlib.scopes.readings``, and the busy and
collective times ``benchlib.tracefile`` reads from the same trace.  It
checks nothing against the reference; ``bench/run.py`` does that.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def window(served, seconds: float, ann):
    """Requests back to back until ``seconds`` have passed, as the
    benchmark's window serves them: (wall seconds, requests served)."""
    n = 0
    t0 = time.perf_counter()
    with ann("bench.window"):
        while True:
            served.serve(served.request(n), ann)
            n += 1
            if time.perf_counter() >= t0 + seconds:
                break
    return time.perf_counter() - t0, n


def run(workload: str, seed: int, seconds: float, root=None, base=None,
        require_tpu: bool = True):
    """The result of one probe of ``workload``, or an exit code."""
    import jax

    from benchlib import harness, scopes, spec, tracefile
    from repro.launch.compile_cache import enable_compile_cache

    root, base = root or spec.ROOT, base or spec.BENCH
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        harness.err(f"scopes: needs a TPU, JAX found {devices[0].platform}")
        return 2
    cell = harness.load_cell(workload, seed, root, base)
    if len(devices) < cell.chips:
        harness.err(f"scopes: {workload} needs {cell.chips} chips")
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = harness.CompileCounter()
    served = harness.Served(cell, devices)
    served.serve(served.request(-1))
    setup_s = time.perf_counter() - T0
    ann = jax.profiler.TraceAnnotation

    off_s, off_n = window(served, seconds, ann)
    n0 = counter.n
    with harness.traced(root, True) as tr:
        on_s, on_n = window(served, seconds, ann)
    in_window = counter.n - n0

    t = time.perf_counter()
    programs = served.engine._compiler.programs()
    harness.log(f"programs: {len(programs)} step executables lowered and "
                f"compiled again in {time.perf_counter() - t:.1f}s, "
                f"{counter.n - n0 - in_window} compiled or loaded")
    maps = scopes.scope_maps(programs)
    trace = tracefile.read(tr.path)
    modules, program = scopes.read(tr.path)
    ids = [d.id for d in served.devices]
    first = ids[0]
    lo, hi = trace.window()
    summary = tracefile.summarize(trace, ids)
    ops = trace.devices.get(first, [])
    by_scope = scopes.device_scopes(ops, modules.get(first, []), maps, lo, hi)
    idle = scopes.idle_by_span(ops, program, lo, hi)
    steps = on_n * cell.steps
    return {
        "workload": workload, "seed": seed,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": cell.chips},
        "setup_s": setup_s,
        "step_s": {"untraced": off_s / (off_n * cell.steps),
                   "traced": on_s / steps},
        "compiles_in_windows": in_window,
        "readings": scopes.readings(by_scope, idle, steps),
        "device_scopes": by_scope,
        "idle_by_span": idle,
        "busy_s": summary["devices"][first]["busy_s"],
        "window_s": summary["window_s"],
        "collective_exposed_s":
            summary["devices"][first]["collective_exposed_s"],
        "modules": sorted({e.name for e in modules.get(first, [])
                           if lo <= e.start <= hi}),
        "programs": [name for name, _ in programs],
        "program_spans": len(program),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    result = run(args.workload, args.seed, args.seconds)
    if isinstance(result, int):
        return result
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
