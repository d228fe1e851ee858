"""Readings that the limits of ``correct`` are set from (not part of a
benchmark run).

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        [--control] [--out chiprun_out/calib.jsonl]

One process builds the cell's engine once and, for each seed, makes that
seed's weights, serves the first request of that seed's stream through
``submit``/``run`` (the window's own programs), and prints the numbers the
benchmark compares: the program against the plain reference.  With
``--control`` it also prints the control's numbers: the reference computed
with float8 e4m3 linear layers in place of the program, against the
float32 reference, on the same request.  Needs the chips the cell asks
for; refuses to run off a TPU.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def control_readings(ctrl, traj, z_T, steps, k, r, ctx, g):
    """The control's numbers, on the reference trajectory ``traj``."""
    from benchlib.harness import rel

    worst, z = 0.0, traj[0]
    for i in range(1, steps + 1):
        step = ctrl.step(traj[i - 1], i, steps, k, r, ctx, g)
        worst = max(worst, rel(step, traj[i], traj[i] - traj[i - 1]))
        # the control's own trajectory: its first step is the one above
        z = step if i == 1 else ctrl.step(z, i, steps, k, r, ctx, g)
    return {"served_err": rel(z, traj[-1], traj[-1] - z_T),
            "step_err": worst}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchlib import harness, reference

    seeds = [int(s) for s in args.seeds.split(",")]
    devices = jax.devices()
    cell = harness.load_cell(args.workload, seeds[0])
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"calibrate: needs {cell.chips} TPU chips, found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    served = harness.Served(cell, devices)
    served.serve(served.request(-1))
    print(f"calibrate: {args.workload} engine warm after "
          f"{time.perf_counter() - T0:.1f}s", flush=True)
    tr = cell.traffic
    axis = None if served.mesh is None else "data"
    sharding = jax.tree.leaves(served.params)[0].sharding
    out = open(args.out, "a") if args.out else None
    for seed in seeds:
        cell.seed = seed
        served.params = served.engine.params = None
        ref = ctrl = None
        gc.collect()
        wkey = int(cell.rng("weights").integers(0, 2 ** 31 - 1))
        served.params = cell.model.make_params(jax.random.PRNGKey(wkey),
                                               cell.arch, sharding)
        served.engine.params = served.params
        served._req_rng = cell.rng("requests")
        req = served.request(0)
        t = time.perf_counter()
        latent = np.asarray(served.serve(req), np.float64)[0]
        serve_s = time.perf_counter() - t
        ref = reference.Reference(cell.model, cell.arch, served.params,
                                  served.mesh, axis)
        t = time.perf_counter()
        got, traj = harness.check_request(served, req, latent, ref)
        line = {"workload": args.workload, "seed": seed, "program": got,
                "replay_compiles": served.replay_compiles,
                "serve_s": serve_s,
                "check_s": time.perf_counter() - t}
        if args.control:
            ctrl = reference.Reference(cell.model, cell.arch, served.params,
                                       served.mesh, axis, quant="fp8")
            t = time.perf_counter()
            ctx = harness.request_context(req)
            line["control"] = control_readings(
                ctrl, traj, traj[0], cell.steps, tr["partitions"],
                tr["overlap"], ctx, req.guidance)
            line["control_s"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
