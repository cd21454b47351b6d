"""CPU of the process pool's result channel per row the loader delivered,
over the run: the workers' pickle-5 framing and send of each result
(``serialize``) and the consumer's receive and rebuild (``deserialize``),
as the program counts them (``lib/program.py``)."""

from lib import program


def read(run):
    return program.stage_cpu_ms_per_row(run, ('serialize', 'deserialize'))
