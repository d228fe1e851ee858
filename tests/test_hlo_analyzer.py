"""Validate the trip-count-aware HLO analyzer against XLA's own
cost_analysis on fully-unrolled programs (where XLA counts correctly)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo_analyzer import analyze
from repro.compat import cost_analysis


def _flops_xla(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    return cost_analysis(c).get("flops", 0.0), c.as_text()


def test_single_matmul():
    x = jnp.zeros((128, 256), jnp.float32)
    w = jnp.zeros((256, 64), jnp.float32)
    ref, hlo = _flops_xla(lambda a, b: a @ b, x, w)
    a = analyze(hlo)
    assert a.flops == pytest.approx(ref, rel=0.01)
    assert a.flops == 2 * 128 * 256 * 64


def test_scan_trip_count_multiplies():
    x = jnp.zeros((64, 64), jnp.float32)

    def rolled(c):
        def body(c, _):
            return c @ x, None
        out, _ = jax.lax.scan(body, c, None, length=13)
        return out

    ref_unrolled, _ = _flops_xla(
        lambda c: jax.lax.scan(lambda c, _: (c @ x, None), c, None,
                               length=13, unroll=True)[0], x)
    _, hlo_rolled = _flops_xla(rolled, x)
    a = analyze(hlo_rolled)
    assert a.flops == pytest.approx(ref_unrolled, rel=0.02), \
        f"analyzer {a.flops} vs unrolled xla {ref_unrolled}"


def test_nested_scan():
    x = jnp.zeros((32, 32), jnp.float32)

    def nested(c):
        def outer(c, _):
            def inner(c, _):
                return jnp.tanh(c @ x), None
            c, _ = jax.lax.scan(inner, c, None, length=4)
            return c, None
        out, _ = jax.lax.scan(outer, c, None, length=5)
        return out

    _, hlo = _flops_xla(nested, x)
    a = analyze(hlo)
    expected = 2 * 32 * 32 * 32 * 4 * 5
    assert a.flops == pytest.approx(expected, rel=0.05)


def test_batched_dot_and_einsum():
    q = jnp.zeros((4, 8, 16, 32), jnp.float32)
    k = jnp.zeros((4, 8, 64, 32), jnp.float32)
    ref, hlo = _flops_xla(
        lambda q, k: jnp.einsum("bhqd,bhkd->bhqk", q, k), q, k)
    a = analyze(hlo)
    assert a.flops == pytest.approx(ref, rel=0.01)


def test_model_forward_matches_unrolled_xla():
    """End-to-end: reduced granite loss.  (1) The analyzer must give the
    SAME answer on rolled and unrolled lowerings (trip-count correctness);
    (2) its MXU (dot/conv) flops must account for the majority of XLA's
    total flop count on the unrolled program (the remainder is elementwise
    VPU work, which the roofline attributes to the memory term)."""
    from repro import models
    from repro.configs import get_config
    from repro.models import scan_util

    cfg = get_config("granite-3-2b").reduced()
    model = models.build(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 64), jnp.int32)}

    rolled_hlo = jax.jit(model.loss).lower(params, batch).compile().as_text()
    scan_util.set_unroll(True)
    try:
        unrolled = jax.jit(model.loss).lower(params, batch).compile()
    finally:
        scan_util.set_unroll(False)
    ref_total = cost_analysis(unrolled).get("flops", 0.0)
    a_rolled = analyze(rolled_hlo)
    a_unrolled = analyze(unrolled.as_text())
    assert a_rolled.flops == pytest.approx(a_unrolled.flops, rel=0.02), \
        "trip-count accounting diverges from true unrolling"
    # XLA's aggregate includes elementwise VPU flops but models some dots
    # differently on CPU; same order of magnitude is the sanity bar — the
    # exact-dot unit tests above pin correctness precisely.
    assert 0.5 * ref_total < a_rolled.flops < 1.5 * ref_total, \
        f"dot flops {a_rolled.flops:.3e} vs xla total {ref_total:.3e}"


def test_collectives_inside_while_multiply():
    """psum inside a scan must count trip_count times."""
    import subprocess, sys, textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.analysis.hlo_analyzer import analyze
        from repro import compat
        mesh = compat.make_mesh((4,), ("x",))
        def f(v):
            def body(c, _):
                return c + jax.lax.psum(c, "x"), None
            out, _ = jax.lax.scan(body, v, None, length=7)
            return out
        sm = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False)
        hlo = jax.jit(sm).lower(
            jax.ShapeDtypeStruct((128,), jnp.float32)).compile().as_text()
        a = analyze(hlo)
        n = a.collective_counts.get("all-reduce", 0)
        assert n == 7, f"expected 7 all-reduces, got {n}"
        b = a.collective_bytes.get("all-reduce", 0)
        assert b == 7 * 128 * 4, b
        print("OK")
    """)
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, cwd="/root/repo",
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert res.returncode == 0, res.stderr
    assert "OK" in res.stdout
