"""Roofline share of the LP stitch kernel (``kernels/latent_blend``), in
percent: the least time its bytes need at the HBM peak, summed over the
stitches of the window, over the summed device time of its events in
the trace.  The kernel is memory-bound (one multiply-add per element)."""
from benchlib import flops

MARK = "latent_blend"


def read(rec):
    events, trace = rec["events"], rec["trace"]
    if events is None or rec["peaks"] is None:
        return None
    lo_hi = events.window()
    spent = 0.0
    for dev in trace["devices"]:
        for e in events.devices.get(dev, []):
            if e.op.startswith(MARK):
                lo, hi = max(e.start, lo_hi[0]), min(e.end, lo_hi[1])
                spent += max(0.0, hi - lo) * 1e-9
    if spent <= 0.0:
        return None
    need = sum(flops.latent_blend_bytes(c["k"], c["window"], c["extent"],
                                        c["rest"]) for c in rec["stitch"])
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / spent
