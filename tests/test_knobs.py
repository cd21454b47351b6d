"""Knob registry (telemetry/knobs.py): the one owner of PETASTORM_TPU_*
parsing. Regression coverage for the call sites the env-knob analysis
pass migrated onto it — semantics must match the old per-site parses."""

import pytest

from petastorm_tpu.analysis.contracts import KNOWN_KNOBS
from petastorm_tpu.telemetry import knobs


def test_unregistered_knob_raises():
    with pytest.raises(ValueError, match='Unregistered'):
        knobs.raw('PETASTORM_TPU_NOT_A_REAL_KNOB')
    with pytest.raises(ValueError, match='Unregistered'):
        knobs.set_env('PETASTORM_TPU_NOT_A_REAL_KNOB', '1')


def test_raw_and_get_str(monkeypatch):
    monkeypatch.delenv('PETASTORM_TPU_STAGING_SLOTS', raising=False)
    assert knobs.raw('PETASTORM_TPU_STAGING_SLOTS') is None
    assert knobs.get_str('PETASTORM_TPU_STAGING_SLOTS') == ''
    monkeypatch.setenv('PETASTORM_TPU_STAGING_SLOTS', '  0  ')
    assert knobs.raw('PETASTORM_TPU_STAGING_SLOTS') == '  0  '
    assert knobs.get_str('PETASTORM_TPU_STAGING_SLOTS') == '0'


@pytest.mark.parametrize('value,disabled', [
    ('0', True), ('false', True), ('off', True), ('no', True),
    ('FALSE', True), (' off ', True),
    ('', False), ('1', False), ('anything', False),
])
def test_is_disabled_spellings(monkeypatch, value, disabled):
    monkeypatch.setenv('PETASTORM_TPU_METRICS', value)
    assert knobs.is_disabled('PETASTORM_TPU_METRICS') is disabled


@pytest.mark.parametrize('value,enabled', [
    ('1', True), ('true', True), ('on', True), ('yes', True), ('ON', True),
    ('', False), ('0', False), ('anything', False),
])
def test_is_enabled_spellings(monkeypatch, value, enabled):
    monkeypatch.setenv('PETASTORM_TPU_TRACE', value)
    assert knobs.is_enabled('PETASTORM_TPU_TRACE') is enabled


def test_get_int_fallback_and_floor(monkeypatch):
    name = 'PETASTORM_TPU_STAGING_SLOTS'
    monkeypatch.delenv(name, raising=False)
    assert knobs.get_int(name, 2) == 2
    monkeypatch.setenv(name, '7')
    assert knobs.get_int(name, 2) == 7
    monkeypatch.setenv(name, 'seven')       # unparseable -> default
    assert knobs.get_int(name, 2) == 2
    monkeypatch.setenv(name, '1')
    assert knobs.get_int(name, 2, floor=2) == 2


def test_get_float_fallback(monkeypatch):
    name = 'PETASTORM_TPU_METRICS_WINDOW_S'
    monkeypatch.setenv(name, '0.25')
    assert knobs.get_float(name, 0.5) == 0.25
    monkeypatch.setenv(name, 'fast')
    assert knobs.get_float(name, 0.5) == 0.5


def test_set_env_round_trip(monkeypatch):
    # setenv FIRST so monkeypatch records the true original for teardown
    # (delenv on an already-missing name records nothing, and undo would
    # then RESTORE the set_env write — leaking TRACE=1 into later tests)
    monkeypatch.setenv('PETASTORM_TPU_TRACE', '0')
    knobs.set_env('PETASTORM_TPU_TRACE', '1')
    assert knobs.is_enabled('PETASTORM_TPU_TRACE')


def test_every_registered_knob_is_prefixed():
    assert all(name.startswith(knobs.KNOB_PREFIX) for name in KNOWN_KNOBS)


# -- migrated call sites keep their semantics --------------------------------


def test_native_disabled_semantics(monkeypatch):
    from petastorm_tpu.native import native_disabled
    monkeypatch.delenv('PETASTORM_TPU_NATIVE', raising=False)
    assert native_disabled() is False           # default: on
    monkeypatch.setenv('PETASTORM_TPU_NATIVE', '0')
    assert native_disabled() is True            # live per-call check
    monkeypatch.setenv('PETASTORM_TPU_NATIVE', 'no')
    assert native_disabled() is True            # shared DISABLED_VALUES
    monkeypatch.setenv('PETASTORM_TPU_NATIVE', '1')
    assert native_disabled() is False


def test_staging_knobs_via_refresh(monkeypatch):
    from petastorm_tpu.jax import staging
    monkeypatch.setenv('PETASTORM_TPU_STAGING_SLOTS', '5')
    staging.refresh_staging()
    try:
        assert staging.staging_slots() == 5
        monkeypatch.setenv('PETASTORM_TPU_STAGING_SLOTS', '1')  # under floor
        staging.refresh_staging()
        assert staging.staging_slots() == 2
        monkeypatch.setenv('PETASTORM_TPU_STAGING_SLOTS', '')
        staging.refresh_staging()
        assert staging.staging_slots() == 2    # unset: the default
    finally:
        monkeypatch.delenv('PETASTORM_TPU_STAGING_SLOTS', raising=False)
        staging.refresh_staging()


def test_stall_window_knob(monkeypatch):
    from petastorm_tpu.telemetry.stall import default_window_s
    monkeypatch.delenv('PETASTORM_TPU_METRICS_WINDOW_S', raising=False)
    assert default_window_s() == 0.5
    monkeypatch.setenv('PETASTORM_TPU_METRICS_WINDOW_S', '0.25')
    assert default_window_s() == 0.25
    monkeypatch.setenv('PETASTORM_TPU_METRICS_WINDOW_S', '-1')
    assert default_window_s() == 0.5            # non-positive -> default
    monkeypatch.setenv('PETASTORM_TPU_METRICS_WINDOW_S', 'soon')
    assert default_window_s() == 0.5


def test_autodump_windows_knob(monkeypatch):
    from petastorm_tpu.telemetry.tracing import autodump_windows
    monkeypatch.delenv('PETASTORM_TPU_TRACE_AUTODUMP_WINDOWS', raising=False)
    assert autodump_windows() == 6
    monkeypatch.setenv('PETASTORM_TPU_TRACE_AUTODUMP_WINDOWS', '3')
    assert autodump_windows() == 3
    monkeypatch.setenv('PETASTORM_TPU_TRACE_AUTODUMP_WINDOWS', '0')
    assert autodump_windows() == 1              # floor
    monkeypatch.setenv('PETASTORM_TPU_TRACE_AUTODUMP_WINDOWS', 'many')
    assert autodump_windows() == 6
