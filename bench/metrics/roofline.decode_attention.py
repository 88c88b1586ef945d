"""``decode_attention`` (``kernels/decode_attention.py``) share of its
roofline in the traced training window (``bench/flops/decode_attention.py``).
At the bitseq shapes the trace places every operand of the kernel on chip
(layout ``S(1)``), so no HBM bytes are counted and the reading is its
compute share against the bf16 peak alone: a lower bound, which says
nothing of the bandwidth the kernel uses between on-chip memories."""
import re

from bench import trace

#: the kernel's custom call as the trace names it: the instruction the
#: jitted ``ops.decode_attention`` wrapper produces
PATTERN = re.compile(r"^%decode_attention(\.\d+)? = .*tpu_custom_call")


def read(run):
    if run.reduction is None:
        return None
    return trace.roofline_share(run.reduction, PATTERN,
                                run.flops("decode_attention").ops, run.peaks)
