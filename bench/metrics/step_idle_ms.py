"""Device-idle time inside the program's ``dispatch.step`` spans in the
traced window, per step that started in it: the host's share of a step
that the device waits through (``bench/spans.py``).  Nothing without the
program's spans or a device plane."""

from bench import spans


def read(run):
    program = spans.of_run(run)
    if not program:
        return None
    got = spans.idle_in(run.trace, program)
    if got is None:
        return None
    idle_ns, steps = got
    return 1e-6 * idle_ns / steps
