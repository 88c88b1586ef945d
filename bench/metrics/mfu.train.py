"""Model FLOP/s utilization of a whole training step: the configuration's
model operations per trajectory (``bench/flops/<step_flops>.py``) times
the trajectories per second of the traced window, over the chip's bf16
peak, in percent.  The programs compute in float32, so this is a lower
bound of the share of what the chip could do at their precision."""


def read(run):
    f = run.flops(run.config["step_flops"]).flops_per_traj(run.config)
    rate = run.host.get("traj_per_s")
    return None if not rate else 100.0 * f * rate / run.peaks["flops_per_s"]
