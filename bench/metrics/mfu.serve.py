"""Model FLOP/s utilization of the engine's blocks: the model operations
of the samples served in the traced window (``bench/flops/decode_step.py``,
attention over live slots) over the device time of the engine's compiled
blocks in the trace times the bf16 peak, in percent.  Taken per block and
not from the served rate, which the cell fixes below the knee."""
import re

PATTERN = re.compile(r"jit_block|jit\(block\)")


def read(run):
    r = run.reduction
    if r is None:
        return None
    n, t = r.module_time(PATTERN)
    samples = run.host.get("samples_served", 0)
    if n == 0 or t <= 0 or not samples:
        return None
    f = run.flops("decode_step").model_flops_per_sample(run.config)
    return 100.0 * samples * f / (t * run.peaks["flops_per_s"])
