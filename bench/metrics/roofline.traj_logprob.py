"""``traj_logprob`` (``kernels/traj_logprob.py``) share of its roofline in
the traced training window, over its forward and backward call of each
step; bandwidth-bound (``bench/flops/traj_logprob.py``)."""
import re

from bench import trace

#: the kernel's custom call by its signature: it returns the (B, 1, 1)
#: totals and (B, T, 1) per-step log-probs (its instruction is named after
#: the custom VJP, ``jvp__``)
PATTERN = re.compile(r"^%\S+ = \(f32\[\d+,1,1\]\{[^}]*\}, "
                     r"f32\[\d+,\d+,1\]\{[^}]*\}\) custom-call\("
                     r".*tpu_custom_call")


def read(run):
    if run.reduction is None:
        return None
    return trace.roofline_share(run.reduction, PATTERN,
                                run.flops("traj_logprob").ops, run.peaks)
