"""CPU of the host-to-device dispatch (``h2d_dispatch``: ``device_put``
and the runtime's work on the calling thread) per row the loader
delivered, over the run, as the program counts it (``lib/program.py``)."""

from lib import program


def read(run):
    return program.stage_cpu_ms_per_row(run, ('h2d_dispatch',))
