"""Model FLOP utilization of a denoise step, in percent: the FLOPs of one
full-latent guided step (conditional and unconditional forward at the
configured widths) over ``step_s`` of the traced run, over chips times
the bf16 peak.  Independent of K and r: LP's window overlap counts as
overhead."""
from benchlib import flops


def read(rec):
    if not rec["steps"] or rec["peaks"] is None:
        return None
    work = flops.guided_step_flops(rec["arch"], rec["latent"])
    return 100.0 * work / rec["step_s"] / (rec["chips"] *
                                           rec["peaks"]["bf16_flops"])
