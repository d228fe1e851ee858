"""Hierarchical hybrid parallelism (paper supplementary §11): inter-group
LP + intra-group tensor parallelism, demonstrated on 8 virtual devices.

Mesh (4, 2) ("data", "model"): 4 LP groups each splitting the latent, 2-way
TP inside each group.  The script lowers the LP step with the explicit
shard_map engine, prints the collective schedule from the compiled HLO
(the proof that only latent-sized tensors cross group boundaries), and
compares the §11 analytic cost model against pure-NMP / pure-TP.

Run:  PYTHONPATH=src python examples/hybrid_parallel.py
(uses 8 virtual CPU devices; re-execs itself to set XLA_FLAGS first)
"""
import os
import sys

if os.environ.get("XLA_FLAGS", "").find("device_count") < 0:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402  (after XLA_FLAGS)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis.hlo_analyzer import analyze  # noqa: E402
from repro.core import comm_model, plan_uniform  # noqa: E402
from repro.core.spmd import lp_forward_shard_map  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def main():
    mesh = make_mesh((4, 2), ("data", "model"))
    print(f"mesh: {dict(mesh.shape)}  (4 LP groups x 2-way TP)")

    # toy "DiT" with an intra-group TP matmul over channels: each TP rank
    # computes half the contraction and the group psums the partials —
    # the Megatron pattern, inside every LP group
    d = 16
    w1 = jnp.eye(d) * 0.1

    def denoise(window):  # runs per device inside shard_map
        tp = jax.lax.axis_index("model")
        half = d // 2
        lo = tp * half
        w_slice = jax.lax.dynamic_slice_in_dim(w1, lo, half, 0)   # (d/2, d)
        x_slice = jax.lax.dynamic_slice_in_dim(window, lo, half, 3)
        partial = jnp.einsum("thwc,cd->thwd", x_slice, w_slice)
        return jax.lax.psum(partial, "model")  # intra-group TP reduce

    plan = plan_uniform(extent=32, patch=2, num_partitions=4,
                        overlap_ratio=0.5, dim=0)
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=(32, 8, 4, 16)).astype(np.float32))

    with jax.set_mesh(mesh):
        fn = jax.jit(lambda zz: lp_forward_shard_map(denoise, zz, plan, 0,
                                                     mesh, "data"))
        compiled = fn.lower(z).compile()
        out = fn(z)
    assert np.isfinite(np.asarray(out)).all()

    a = analyze(compiled.as_text())
    print("\ncompiled collective schedule (per device, one LP step):")
    for kind, nbytes in sorted(a.collective_bytes.items()):
        print(f"  {kind:20} {int(a.collective_counts[kind]):3d} ops  "
              f"{nbytes/2**20:8.2f} MiB")
    sz = z.size * 4 / 2**20
    print(f"  (latent S_z = {sz:.2f} MiB -> reconstruction psum is "
          f"latent-scale, as designed)")

    # ---- the production hybrid engine: halo schedule over the group
    # axis, TP Phi_m as a black box, eager ppermute issue (PR 3)
    from repro.core.hybrid import lp_forward_halo_hybrid

    with jax.set_mesh(mesh):
        fn_h = jax.jit(lambda zz: lp_forward_halo_hybrid(
            denoise, zz, plan, 0, mesh, "data", "model", codec="int8"))
        compiled_h = fn_h.lower(z).compile()
        out_h = fn_h(z)
    np.testing.assert_allclose(np.asarray(out_h), np.asarray(out),
                               atol=0.1 * float(np.abs(out).max()))
    ah = analyze(compiled_h.as_text())
    print("\nhybrid halo engine (int8 wire), same step:")
    for kind, nbytes in sorted(ah.collective_bytes.items()):
        print(f"  {kind:20} {int(ah.collective_counts[kind]):3d} ops  "
              f"{nbytes/2**20:8.2f} MiB")
    print("  (all-reduce = the intra-group TP psum only; LP moved to "
          "overlap-slab ppermutes + a coded core all-gather)")

    # ---- §11 analytic comparison at production scale
    cfgm = comm_model.wan21_comm_config(num_frames=81)
    K = 16
    print(f"\n§11 cost model, WAN2.1 81f on {K} devices:")
    print(f"  pure NMP            : {comm_model.comm_nmp(cfgm, K)/2**30:8.2f} GiB")
    print(f"  pure TP             : {comm_model.comm_tp(cfgm, K)/2**30:8.2f} GiB")
    for M in (2, 4, 8):
        hyb = comm_model.comm_hybrid(cfgm, K, M, 0.5, intra="nmp")
        bound = (K - M) / (K - 1)
        print(f"  LP({M:2d} groups)+NMP   : {hyb/2**30:8.2f} GiB   "
              f"(Eq. 54 bound: {bound:.2f}x of NMP)")
    lp = comm_model.comm_lp_measured(cfgm, K, 0.5)
    print(f"  pure LP (K={K})      : {lp/2**30:8.2f} GiB")


if __name__ == "__main__":
    main()
