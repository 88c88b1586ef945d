"""Host time to enqueue one call of the training loop's jitted step
(``algo/loop.py``): the benchmark's host clock around each call in the
window, with no block; the mean, in milliseconds.  Where the device is
the slower side and the TPU runtime bounds its queue of pending steps, a
call can wait in that queue, and the reading rises towards the device's
step time."""


def read(run):
    d = run.host.get("dispatch_s")
    return 1e3 * sum(d) / len(d) if d else None
