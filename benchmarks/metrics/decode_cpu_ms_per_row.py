"""CPU of the ``decode`` and ``decode_fused`` stages per row the loader
delivered, over the run: each stage's own CPU as the program counts it
(``petastorm_tpu_stage_cpu_seconds_total``, worker processes merged), the
native decoder's pool threads included (``lib/program.py``)."""

from lib import program


def read(run):
    return program.stage_cpu_ms_per_row(run, ('decode', 'decode_fused'))
