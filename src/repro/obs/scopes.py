"""Which DiT sub-block or LP stage owns each instruction of a compiled
step program.

The program names its device work with ``jax.named_scope`` (the
vocabulary is :data:`SCOPES`); XLA keeps the scope path in every
instruction's ``metadata={op_name="..."}`` of the optimized HLO.  A
profile's device op events carry only the instruction's text, without
that metadata, so the program supplies the map: :func:`scope_map` of the
HLO text of each executable that ran (``LPStepCompiler.programs``),
looked up by the module name and instruction name a profile shows.
"""
from __future__ import annotations

import re
from typing import Dict, List

# innermost wins: a DiT sub-block inside the LP window's vmap reads as
# the sub-block, the loop machinery around the blocks as ``dit.blocks``
SCOPES = (
    "dit.embed",        # patchify, projections, time MLP, RoPE tables
    "dit.adaln",        # modulation vectors, the norms, _modulate
    "dit.self_attn",    # q/k/v/o, dit_rope, the flash kernel, gated residual
    "dit.cross_attn",   # cross-attention sub-block with its pre-norm
    "dit.ffn",          # the FFN and its gated residual
    "dit.blocks",       # the scan over blocks: per-block weight slices
    "dit.head",         # final adaLN, norm, head, unpatchify
    "dit.cfg",          # the CFG pair's inputs and the guided combine
    "mmdit.embed",      # MMDiT: patchify, video and vector embedders, RoPE
    "mmdit.refiner",    # the token refiner over the text
    "mmdit.double_attn",  # double-stream modulation, q/k/v, joint attention
    "mmdit.double_mlp",   # double-stream gated MLPs
    "mmdit.single",     # single-stream block: fused projection, attention
    "mmdit.head",       # final adaLN, norm, head, unpatchify
    "mmdit.blocks",     # the scans over both kinds of block
    "lp.window",        # window slicing (the rotating partition)
    "lp.stitch",        # weighting, normalising, reassembly, latent_blend
    "lp.halo",          # the collectives: halo slabs, core gather, psum
    "lp.update",        # the sampler's update of the latent
)
UNSCOPED = "unscoped"

_VOCAB = frozenset(SCOPES)
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def scope_of(op_name: str) -> str:
    """The innermost component of an ``op_name`` path that is in the
    vocabulary, seen through transform wrappers (``vmap(dit.ffn)``), or
    ``unscoped``."""
    for part in reversed(op_name.split("/")):
        while True:
            if part in _VOCAB:
                return part
            m = _WRAPPED.match(part)
            if m is None:
                break
            part = m.group(1)
    return UNSCOPED


def scope_map(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: scope}`` of every instruction in an HLO
    module's text.

    An instruction whose ``op_name`` has no scope, or that has none (an
    op XLA inserted: a copy, a prefetch, a buffer, a fusion it made
    without metadata), takes the scope of the root of the computation it
    calls, else that of its first scoped operand, else that of a scoped
    user: it works for what it reads or for what reads it.  HLO
    text lists callees before callers and operands before users, so a
    pass in text order and one back resolve chains of such ops.
    """
    out: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    root_of: Dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(2)
        rest = line[m.end():]
        operands[name] = _OPERAND.findall(rest.split("metadata=")[0])
        op = _OP_NAME.search(rest)
        scope = UNSCOPED if op is None else scope_of(op.group(1))
        if scope == UNSCOPED:
            called = _CALLS.search(rest)
            if called is not None:
                scope = out.get(root_of.get(called.group(1), ""), UNSCOPED)
        if scope == UNSCOPED:
            scope = next((out[o] for o in operands[name]
                          if out.get(o, UNSCOPED) != UNSCOPED), UNSCOPED)
        out[name] = scope
        if m.group(1) and comp is not None:
            root_of[comp] = name
    for name in reversed(list(out)):
        for o in operands[name]:
            if out.get(o) == UNSCOPED and out[name] != UNSCOPED:
                out[o] = out[name]
    return out
