"""Peak bytes on the fullest chip over the run, set-up included, taken
after the window and before the checks, in GiB: the allocator's peak and
the running program's reserved temporaries together
(`harness/devices.py:memory_peak_bytes`). It decides the batch and the
model a user can fit."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 2 ** 30
