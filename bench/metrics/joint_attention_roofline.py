"""Roofline share of the DiT flash kernel (``kernels/dit_attention``) on an
MMDiT's joint attention, in percent: the least time its real (unpadded)
FLOPs need at the bf16 peak, over the summed device time of the
kernel's events in the window.  The FLOPs per LP window and step are the
``joint_attention_flops`` of the architecture module that the cell's
configuration names (``models/<model>.py``, found by the ``arch`` block
the record carries); windows from ``rec["stitch"]``.  An architecture
without joint attention (no such function) has nothing to read."""
import json
from pathlib import Path

from benchlib import spec

MARK = "dit_flash_attention"
BASE = Path(__file__).resolve().parents[1]


def _count(arch):
    """``joint_attention_flops`` of the module whose configuration runs
    ``arch``, or None."""
    for path in sorted((BASE / "configs").glob("*.json")):
        conf = json.loads(path.read_text())
        if conf.get("arch") == arch:
            return getattr(spec.model(conf["model"], BASE),
                           "joint_attention_flops", None)
    return None


def read(rec):
    events, trace = rec["events"], rec["trace"]
    if events is None or rec["peaks"] is None:
        return None
    count = _count(rec["arch"])
    if count is None:
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
    need = 0
    for c in rec["stitch"]:
        window = list(rec["latent"])
        window[c["dim"]] = c["window"]
        need += c["k"] * count(rec["arch"], window)
    return 100.0 * need / rec["peaks"]["bf16_flops"] / spent
