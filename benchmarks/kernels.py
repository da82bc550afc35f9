"""Which device operations of a reduced trace belong to which kernel.

A Pallas kernel shows in the device's operations line as a custom call
whose target is ``tpu_custom_call``; what tells one kernel from another
is the instruction's name, which the compiler takes from the jax name
stack: the flash-attention calls (forward, backward dq, backward dkv)
sit in the flax module ``attn`` and are named ``%attn.<n>`` (my chip
run, PR 24: 144 of them in the 48-layer step, 36 in the 12-layer one).
The flash kernel is the only Pallas kernel of these programs; a
``name=`` on each ``pl.pallas_call`` would make this exact (PERF.md,
open questions).
"""

import re

KERNELS = {
    "flash": ("tpu_custom_call", re.compile(r"^%?attn(\.|$)")),
}


def kernel_ops(trace, kernel):
    """``{instruction: {"seconds", "count", ...}}`` of one kernel's
    operations in a reduced trace."""
    target, pattern = KERNELS[kernel]
    return {
        name: op for name, op in trace["ops"].items()
        if op["target"] == target and pattern.match(name)
    }


def kernel_seconds_per_step(trace, kernel):
    ops = kernel_ops(trace, kernel)
    if not ops or not trace["steps"]:
        return None
    return sum(op["seconds"] for op in ops.values()) / trace["steps"]
