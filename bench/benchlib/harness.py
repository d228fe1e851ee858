"""One benchmark run: build the engine, warm it, time a closed-loop window
of requests, check what it served against the plain reference, print the
result line.

The engine is built as ``repro.launch.serve.build_engine`` builds it (the
configuration's published widths, the traffic's K, r, steps and batch,
every other engine option at its default), with weights made here from
``--seed``.  One client sends requests back to back: submit, run, block
on the returned latent, next.  The window closes after the first request
that ends past ``--seconds``; every request in it is whole.

What belongs to the configuration's architecture comes from its module
(``Cell.model``, ``bench/models/<model>.py``): the program's forward and
config, the weights, a request's conditioning, the extras of a replayed
step, the reference's prediction and the FLOPs of a step.  This file
holds the engine, the request stream, the window, the check and the
result line, which every architecture shares.
"""
from __future__ import annotations

import dataclasses
import json
import shutil
import sys
import time
from contextlib import contextmanager, nullcontext
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

import numpy as np

from . import reference, spec, tracefile
from .peaks import peaks

TRACE_DIR = ".bench_out/trace"


def log(msg: str) -> None:
    print(msg, flush=True)


def err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA compilations (or loads from the persistent cache) of
    this process, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@dataclasses.dataclass
class Cell:
    """A cell's configuration, traffic and the seeded request stream."""

    name: str
    chips: int
    conf: dict
    traffic: dict
    seed: int
    model: ModuleType

    @property
    def arch(self) -> dict:
        return self.conf["arch"]

    @property
    def latent(self):
        return tuple(self.conf["latent"])

    @property
    def steps(self) -> int:
        return self.traffic["steps_per_request"]

    def rng(self, stream: str) -> np.random.Generator:
        """Independent streams of one seed (weights, requests, sample)."""
        tag = int.from_bytes(stream.encode(), "little") % (2 ** 32)
        return np.random.default_rng(np.random.SeedSequence([self.seed, tag]))


def load_cell(name: str, seed: int, root: Path = spec.ROOT,
              base: Path = spec.BENCH) -> Cell:
    bench = spec.benchmark(root)
    c = spec.cell(bench, name)
    conf = spec.config(c["config"], base)
    return Cell(name, c["chips"], conf, spec.traffic(c["traffic"], base),
                seed, spec.model(conf["model"], base))


class Served:
    """The engine under test and the request stream of one run."""

    def __init__(self, cell: Cell, devices):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec, \
            SingleDeviceSharding

        from repro.serving.engine import LPServingEngine

        self.cell = cell
        tr = cell.traffic
        self.mesh = None
        if tr.get("mesh"):
            from repro.launch.mesh import make_hybrid_mesh, parse_mesh

            m, t = parse_mesh(tr["mesh"])
            self.mesh = make_hybrid_mesh(m, t)
            sharding = NamedSharding(self.mesh, PartitionSpec())
            self.devices = list(self.mesh.devices.flat)
        else:
            sharding = SingleDeviceSharding(devices[0])
            self.devices = [devices[0]]
        forward, self.cfg = cell.model.program(cell.conf)
        wkey = int(cell.rng("weights").integers(0, 2 ** 31 - 1))
        self.params = cell.model.make_params(jax.random.PRNGKey(wkey),
                                             cell.arch, sharding)
        self.engine = LPServingEngine(
            forward, self.params, self.cfg,
            num_partitions=tr["partitions"], overlap_ratio=tr["overlap"],
            num_steps=cell.steps, max_batch=tr["max_batch"],
            lp_impl=tr.get("lp_impl", "auto"), mesh=self.mesh)
        self._req_rng = cell.rng("requests")
        self.replay_compiles = 0

    def request(self, rid: int):
        """The next request of the stream: its own noise seed and
        conditioning."""
        import jax

        from repro.serving.engine import VideoRequest

        noise_seed, text_seed = (int(x) for x in
                                 self._req_rng.integers(0, 2 ** 31 - 1, 2))
        ctx = self.cell.model.context(self.cell.arch,
                                      jax.random.PRNGKey(text_seed))
        return VideoRequest(request_id=rid, context=ctx,
                            latent_shape=self.cell.latent, seed=noise_seed,
                            guidance=float(self.cell.traffic["guidance"]))

    def serve(self, req, annotate: Callable = None):
        """Submit, run and block: the served latent as a device array."""
        import jax

        ann = annotate or (lambda name: nullcontext())
        with ann("bench.submit"):
            self.engine.submit(req)
        with ann("bench.run"):
            (res,) = self.engine.run()
        with ann("bench.wait"):
            return jax.block_until_ready(res.latent)

    def program_step(self, req, z: np.ndarray, i: int) -> np.ndarray:
        """Step ``i`` of ``req`` from latent ``z`` (T, H, W, C) through the
        compiled step the window ran, placed as the window placed it."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.core.schedule import rotation_dim, usable_dims

        eng = self.engine
        sampler = eng._sampler
        dims = usable_dims(req.latent_shape, self.cfg.patch_sizes, eng.K)
        sc, t = sampler.step_scalars(i), np.float32(sampler.timestep(i))
        extras = self.cell.model.step_extras(eng, req)
        x = jnp.asarray(np.asarray(z[None], np.float32))
        if i > 1:
            # later steps take the previous step's output: committed to
            # the device, or replicated over the mesh
            x = jax.device_put(x, self.devices[0] if self.mesh is None else
                               NamedSharding(self.mesh, PartitionSpec()))
        fn = eng._compiler.step_fn(rotation_dim(i, dims), x, 1, sc, extras)
        before = fn._cache_size()
        out = np.asarray(fn(x, t, sc, extras), np.float64)[0]
        # the window's own executable, not a new one for other inputs
        self.replay_compiles += fn._cache_size() - before
        return out

    def noise(self, req) -> np.ndarray:
        """The request's initial noise, drawn from its seed as the engine
        draws it (the input, not anything the engine computed)."""
        import jax

        shape = (1, *req.latent_shape, self.cell.arch["latent_channels"])
        return np.asarray(jax.random.normal(jax.random.PRNGKey(req.seed),
                                            shape), np.float64)[0]

    def memory(self) -> List[dict]:
        return [d.memory_stats() or {} for d in self.devices]


def rel(a: np.ndarray, b: np.ndarray, scale: np.ndarray) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(scale))


def request_context(req):
    """The request's conditioning as the reference takes it: each array
    on the host, float32, without the batch dim."""
    import jax

    return jax.tree.map(lambda c: np.asarray(c, np.float32)[0], req.context)


def check_request(served: Served, req, latent: np.ndarray,
                  ref: "reference.Reference"):
    """The numbers that decide ``correct`` for one served request, and
    the reference trajectory ``[z_T, z_1, ...]`` they were read against.

    ``served_err``: the served latent against the reference trajectory
    from the same noise, as a share of the reference's whole move
    ``|z_ref - z_T|``.  ``step_err``: the worst step of the compiled
    steps the window ran, each from the reference's latent before it,
    against the reference's step, as a share of that step's move."""
    tr = served.cell.traffic
    ctx = request_context(req)
    z_T = served.noise(req)
    traj = ref.trajectory(z_T, served.cell.steps, tr["partitions"],
                          tr["overlap"], ctx, req.guidance)
    out = {"served_err": rel(latent, traj[-1], traj[-1] - z_T)}
    worst = 0.0
    for i in range(1, served.cell.steps + 1):
        prog = served.program_step(req, traj[i - 1], i)
        worst = max(worst, rel(prog, traj[i], traj[i] - traj[i - 1]))
    out["step_err"] = worst
    return out, traj


def stitch_calls(cell: Cell, requests: int) -> List[dict]:
    """The stitch geometry of every step the window served."""
    tr, a = cell.traffic, cell.arch
    latent = cell.latent
    out = []
    dims = reference.rotation(latent, a["patch_sizes"], tr["partitions"],
                              cell.steps)
    for _ in range(requests):
        for dim in dims:
            starts, size, _, _ = reference.windows(
                latent[dim], a["patch_sizes"][dim], tr["partitions"],
                tr["overlap"])
            rest = int(np.prod(latent)) // latent[dim] * a["latent_channels"]
            out.append({"dim": dim, "k": len(starts), "window": size,
                        "extent": latent[dim], "rest": rest})
    return out


@contextmanager
def traced(root: Path, on: bool):
    """The profiler around the window when ``on``; yields a holder whose
    ``path`` is the written ``.xplane.pb``."""
    import jax

    class Holder:
        path: Optional[str] = None

    h = Holder()
    if not on:
        yield h
        return
    out = root / TRACE_DIR
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(str(out))
    try:
        yield h
    finally:
        jax.profiler.stop_trace()
        found = sorted(out.rglob("*.xplane.pb"))
        h.path = str(found[-1]) if found else None


def run(workload: str, seed: int, seconds: float, trace: bool, t0: float,
        root: Path = spec.ROOT, base: Path = spec.BENCH,
        require_tpu: bool = True) -> int:
    bench = spec.benchmark(root)
    cell = load_cell(workload, seed, root, base)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        err(f"bench: needs a TPU, JAX found {platform}")
        return 2
    if len(devices) < cell.chips:
        err(f"bench: {workload} needs {cell.chips} chips, JAX found "
            f"{len(devices)}")
        return 2
    kind = devices[0].device_kind
    pk = peaks(kind) if require_tpu else None

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    log(f"bench: {workload} seed={seed} seconds={seconds} trace={int(trace)} "
        f"device={platform} {kind} x{len(devices)} cache={cache}")

    served = Served(cell, devices)
    eng = served.engine
    log(f"engine: lp_impl={eng.lp_impl} K={eng.K} r={eng.r} "
        f"steps={cell.steps} max_batch={eng.max_batch} "
        f"mesh={None if served.mesh is None else dict(served.mesh.shape)}")
    # warm-up: one request of the window's own shape and step count
    served.serve(served.request(-1))
    setup_s = time.perf_counter() - t0
    log(f"setup: {setup_s:.3f}s, {eng._compiler.compiles} step programs, "
        f"{counter.n} programs compiled or loaded")

    ann = jax.profiler.TraceAnnotation
    latents, reqs, failed = [], [], 0
    comp0, back0 = eng._compiler.compiles, counter.n
    with traced(root, trace) as tr:
        t_start = time.perf_counter()
        deadline = t_start + seconds
        with ann("bench.window"):
            while True:
                req = served.request(len(reqs))
                reqs.append(req)
                try:
                    latents.append(served.serve(req, ann))
                except Exception as e:          # a failed request
                    failed += 1
                    latents.append(None)
                    err(f"request {req.request_id} failed: {e!r}")
                if time.perf_counter() >= deadline:
                    break
        t_end = time.perf_counter()
    window_s = t_end - t_start
    done = len(reqs) - failed
    steps = done * cell.steps
    step_s = window_s / steps if steps else float("nan")
    window_compiles = eng._compiler.compiles - comp0
    window_backend = counter.n - back0
    log(f"window: {window_s:.3f}s, {len(reqs)} requests ({failed} failed), "
        f"{steps} steps, step_s={step_s:.4f}; compiled in window: "
        f"{window_compiles} step programs, {window_backend} programs")
    memory = served.memory()
    peak_bytes = max(m.get("peak_bytes_in_use", 0) +
                     m.get("peak_bytes_reserved", 0) for m in memory)

    # the check, once the window has closed and the memory peak is read
    t_check = time.perf_counter()
    sample_rng = cell.rng("sample")
    finished = [j for j, x in enumerate(latents) if x is not None]
    n_check = min(cell.traffic.get("check_requests", 1), len(finished))
    picked = sorted(sample_rng.choice(finished, n_check, replace=False)) \
        if finished else []
    ref = reference.Reference(cell.model, cell.arch, served.params,
                              served.mesh,
                              None if served.mesh is None else "data")
    readings: Dict[str, float] = {}
    for j in picked:
        lat = np.asarray(latents[j], np.float64)[0]
        got, _ = check_request(served, reqs[j], lat, ref)
        for k, v in got.items():
            readings[k] = max(readings.get(k, 0.0), v)
    check_compiles = counter.n - back0 - window_backend
    log(f"check: {len(picked)} of {done} requests against the reference in "
        f"{time.perf_counter() - t_check:.1f}s; {check_compiles} programs "
        f"compiled or loaded for it; the replayed steps compiled "
        f"{served.replay_compiles} (the window's own programs)")

    lim = spec.limits(workload, base) or {}
    compared = {name: {"value": readings.get(name),
                       "limit": lim.get(name, {}).get("limit")}
                for name in ("served_err", "step_err")}
    compared["failed_requests"] = {"value": failed, "limit": 0}
    compared["compiles_in_window"] = {
        "value": window_compiles + window_backend, "limit": 0}
    compared["compiles_in_replay"] = {"value": served.replay_compiles,
                                      "limit": 0}
    ok = bool(picked) and all(
        c["value"] is not None and c["limit"] is not None
        and np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in compared.values())

    rec = {
        "arch": cell.arch, "latent": cell.latent, "chips": cell.chips,
        "step_flops": cell.model.step_flops(cell.arch, cell.latent),
        "peaks": pk, "step_s": step_s, "setup_s": setup_s,
        "steps": steps, "requests": done, "window_s": window_s,
        "memory": memory, "stitch": stitch_calls(cell, done),
        "trace": None, "events": None,
    }
    device = {"platform": platform, "kind": kind, "count": cell.chips,
              "memory_peak_bytes": int(peak_bytes)}
    breakdown = None
    if trace:
        ids = [d.id for d in served.devices]
        events = tracefile.read(tr.path)
        summary = tracefile.summarize(events, ids)
        rec["trace"], rec["events"] = summary, events
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = summary["breakdown"]
        shutil.rmtree(root / TRACE_DIR, ignore_errors=True)
    metrics = {}
    for m in spec.metrics_for(bench, workload, trace):
        value = spec.reader(m["name"], base)(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {"correct": bool(ok), "attempted": len(reqs), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = compared
    for name, c in compared.items():
        err(f"check {name}={c['value']} limit={c['limit']}")
    print(json.dumps(result), flush=True)
    return 0
