"""``subtb_loss`` (``kernels/subtb_loss.py``) share of its roofline in the
traced training window; compute-bound by its counts, and in practice by
its launch (``bench/flops/subtb_loss.py``)."""
import re

from bench import trace

#: the kernel's custom call by its signature: (B, 1, 1) losses from the
#: lengths and the two views of the potentials
PATTERN = re.compile(r"^%\S+ = f32\[\d+,1,1\]\{[^}]*\} custom-call\("
                     r"s32\[\d+\].*tpu_custom_call")


def read(run):
    if run.reduction is None:
        return None
    return trace.roofline_share(run.reduction, PATTERN,
                                run.flops("subtb_loss").ops, run.peaks)
