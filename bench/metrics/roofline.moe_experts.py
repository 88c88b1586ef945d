"""The held experts' grouped matmul (``models/moe.held_experts``, XLA's TPU
``ragged-dot`` kernels) share of its roofline in the traced training
window, over its forward and backward calls: per call the larger of its
operations over peak FLOP/s and its HBM bytes over peak bandwidth
(``bench/flops/moe_experts.py``, rows at the held experts' share of the
routing), summed, over the calls' traced time."""
import re

from bench import trace

#: the grouped matmuls, not the kernel that lays out their groups
PATTERN = re.compile(r"^%ragged-dot-(?!metadata)\S* = .* custom-call\(")


def read(run):
    if run.reduction is None:
        return None
    f = run.flops("moe_experts")
    least = total = 0.0
    for text, (n, t) in run.reduction.ops.items():
        if not PATTERN.search(text):
            continue
        ops, byts = f.cost(*trace.custom_call_types(text), run.config)
        least += n * max(ops / run.peaks["flops_per_s"],
                         byts / run.peaks["hbm_bytes_per_s"])
        total += t
    return 100.0 * least / total if total > 0 else None
