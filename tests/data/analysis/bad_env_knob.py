"""Fixture: raw env reads of the knob namespace + an unregistered knob."""

import os

from petastorm_tpu.telemetry import knobs

# finding: raw os.environ.get outside telemetry/knobs.py
_RAW_GET = os.environ.get('PETASTORM_TPU_STAGING_SLOTS', '')

# finding: raw subscript read
_RAW_SUB = os.environ['PETASTORM_TPU_METRICS']

# finding: raw os.getenv
_RAW_GETENV = os.getenv('PETASTORM_TPU_TRACE')

# finding: membership read
_RAW_IN = 'PETASTORM_TPU_NATIVE' in os.environ

# finding: registry API but an unregistered knob name
_UNREGISTERED = knobs.get_str('PETASTORM_TPU_NOT_A_REAL_KNOB')

# clean: registry API with a registered knob
_OK = knobs.get_str('PETASTORM_TPU_METRICS')
