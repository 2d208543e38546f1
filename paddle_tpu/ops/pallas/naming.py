"""Stable names for the Pallas kernels, as a device trace shows them.

On a TPU a `pallas_call` is a custom call (`tpu_custom_call`), and the
trace names each `XLA Ops` event by its HLO instruction's text. Two
spellings put a kernel's name into that text (seen on the v5e's
compiler, JAX 0.9.0):

- `name=` joins the name stack: the instruction is called after it
  (`%jvp_flash_causal_fwd_.3` for `%jvp__.3`) and `op_name` ends in it;
- `metadata={"kernel_name": ...}` travels as the call's
  `kernel_metadata` frontend attribute and leaves the instruction's own
  name alone.

`name=` alone is not enough for a reduction that looks for
`kernel_name` in the text, and it RENAMES the instruction, which a
reduction anchored on the enclosing program's name (the paged decode
kernel is found as `%engine_decode_step.N`) would lose. So every kernel
carries the attribute, and takes `name=` too unless `rename=False`.
"""
from __future__ import annotations


def kernel_name(name, rename=True):
    """Keyword arguments that name one `pl.pallas_call`."""
    kw = {"metadata": {"kernel_name": name}}
    if rename:
        kw["name"] = name
    return kw
