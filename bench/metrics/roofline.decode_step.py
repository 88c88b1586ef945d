"""``decode_step`` (``decode_step_pallas``) share of its roofline in the
traced serving window, one call per engine micro-step over all lanes;
bandwidth-bound (``bench/flops/decode_step.py``)."""
import re

from bench import trace

#: the fused step's custom call by its signature: it returns the (B, 1)
#: actions and log-probs first
PATTERN = re.compile(r"^%\S+ = \(s32\[\d+,1\]\{[^}]*\}, f32\[\d+,1\]"
                     r".*custom-call\(.*tpu_custom_call")


def read(run):
    if run.reduction is None:
        return None
    return trace.roofline_share(run.reduction, PATTERN,
                                run.flops("decode_step").ops, run.peaks)
