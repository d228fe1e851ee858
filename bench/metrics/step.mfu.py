"""Model FLOP utilization of a denoise step, in percent: the FLOPs of one
full-latent step at the configured widths (``step_flops`` of the
configuration's architecture module, which the harness puts in
``rec["step_flops"]``; for a CFG model the conditional and unconditional
forward) over ``step_s`` of the traced run, over chips times the bf16
peak.  Independent of K and r: LP's window overlap counts as overhead."""


def read(rec):
    if not rec["steps"] or rec["peaks"] is None:
        return None
    return 100.0 * rec["step_flops"] / rec["step_s"] / (
        rec["chips"] * rec["peaks"]["bf16_flops"])
