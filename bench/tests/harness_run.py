"""Drive one harness run on a tiny cell without the TPU check and return
its result line (also runnable as a script, for runs that need four
virtual CPU devices in a process of their own)."""
from __future__ import annotations

import io
import json
import sys
import time
from contextlib import redirect_stdout
from pathlib import Path


def run_cell(tree: Path, cell: str, seed: int = 2 ** 31 + 11,
             seconds: float = 0.5) -> dict:
    from benchlib import harness

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = harness.run(cell, seed, seconds, False, time.perf_counter(),
                         root=tree, base=tree, require_tpu=False)
    assert rc == 0, buf.getvalue()
    return json.loads(buf.getvalue().strip().splitlines()[-1])


if __name__ == "__main__":
    # python harness_run.py <tree> <cell> [no_exchange]
    import os

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    here = Path(__file__).resolve().parent
    sys.path[:0] = [str(here.parent), str(here.parents[1] / "src"), str(here)]
    if sys.argv[3:] == ["no_exchange"]:
        import jax
        import jax.numpy as jnp

        # the halo exchange between chips left out: every ppermute
        # delivers zeros instead of the neighbour's slab
        jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
    print(json.dumps(run_cell(Path(sys.argv[1]), sys.argv[2])))
