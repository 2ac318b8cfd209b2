"""The CountSketch kernel's device ops, as the chip's trace names them."""
import re

# On a v5e the kernel's op is the custom call named after the jitted wrapper
# of its pallas_call: "countsketch_apply.<k> [tpu_custom_call]" (one for A,
# one for b).
KERNEL = re.compile(r"^countsketch_apply(\.\d+)? \[tpu_custom_call\]$")


def is_kernel(name: str) -> bool:
    return bool(KERNEL.match(name))


def per_solve_seconds(run):
    """Device seconds of the kernel per solve (per chip), or None where the
    window ran no such kernel or no solve."""
    solves = run.records.get("solves")
    if run.trace is None or not solves or not run.trace.count(is_kernel):
        return None
    return run.trace.op_seconds(is_kernel) / len(solves)
