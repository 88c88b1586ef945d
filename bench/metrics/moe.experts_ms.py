"""Device time of the held experts' grouped matmuls (forward and backward,
rollout and objective) per training step in the traced window, in
milliseconds."""
from bench import harness


def read(run):
    if run.reduction is None or not run.host.get("steps"):
        return None
    n, t = run.reduction.op_time(
        harness.metric_reader("roofline.moe_experts").PATTERN)
    return 1e3 * t / run.host["steps"] if n else None
