"""Source hygiene enforced with the stdlib (flake8/mypy aren't on the TPU
image; `setup.cfg`/`mypy.ini` configure them for CI — this keeps the cheap
invariants locally enforced)."""

import ast
import glob
import os
import re
import tokenize

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SOURCES = sorted(
    glob.glob(os.path.join(REPO, 'petastorm_tpu', '**', '*.py'),
              recursive=True)
    + glob.glob(os.path.join(REPO, 'examples', '**', '*.py'), recursive=True)
    + glob.glob(os.path.join(REPO, 'tests', '*.py'))
    + glob.glob(os.path.join(REPO, 'tools', '*.py'))
    + [os.path.join(REPO, p) for p in ('setup.py', 'bench.py',
                                       '__graft_entry__.py')])

MAX_LINE = 120


def _read(path):
    with tokenize.open(path) as f:  # honors coding declarations
        return f.read()


def test_sources_found():
    assert len(SOURCES) > 60


def test_all_sources_parse():
    for path in SOURCES:
        ast.parse(_read(path), filename=path)


def test_no_tabs_no_overlong_lines():
    offenders = []
    for path in SOURCES:
        for lineno, line in enumerate(_read(path).splitlines(), 1):
            if '\t' in line:
                offenders.append('%s:%d: tab' % (path, lineno))
            if len(line) > MAX_LINE:
                offenders.append('%s:%d: %d chars' % (path, lineno, len(line)))
    assert not offenders, '\n'.join(offenders)


def _package_sources():
    for path in SOURCES:
        rel = os.path.relpath(path, REPO)
        if rel.startswith('petastorm_tpu'):
            yield rel, _read(path)


def _call_name(node):
    if isinstance(node.func, ast.Name):
        return node.func.id
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    return None


def test_span_and_trace_stage_names_are_canonical():
    """Every literal stage/event name recorded by the package — span(...)
    and the tracing record_* calls — must be in the canonical sets of
    analysis/contracts.py (the ONE source of truth telemetry imports at
    runtime and the pipecheck analyzer verifies statically; or the
    explicit whitelist below): a typo'd stage would silently fall out of
    pipeline_report's canonical grouping and out of the timeline's known
    tracks. The canonical-name analysis pass enforces the same contract
    with constant resolution; this test stays as the dumb independent
    check that would catch the analyzer itself regressing."""
    from petastorm_tpu.analysis.contracts import EVENT_NAMES, STAGES
    whitelist = set()  # intentionally empty today; add with a comment why
    allowed = set(STAGES) | set(EVENT_NAMES) | whitelist
    recording_calls = ('span', 'timed', 'record_complete',
                       'record_instant')
    offenders = []
    for rel, source in _package_sources():
        for node in ast.walk(ast.parse(source, filename=rel)):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if _call_name(node) not in recording_calls:
                continue
            first = node.args[0]
            if isinstance(first, ast.Constant) and \
                    isinstance(first.value, str) and \
                    first.value not in allowed:
                offenders.append('%s:%d: %r' % (rel, node.lineno,
                                                first.value))
    assert not offenders, \
        'unknown stage/event names (add to STAGES/EVENT_NAMES or ' \
        'whitelist): %s' % offenders


def test_every_canonical_stage_is_recorded_somewhere():
    """The reverse custody check: every ``contracts.STAGES`` member is
    actually instrumented — it appears as the literal first argument of
    at least one ``span(...)``/``record_complete(...)``/
    ``record_instant(...)`` call in the package. A stage that exists
    only in the contract would make pipeline_report and the
    critical-path engine silently blind to it (the ISSUE 19 lifeline
    reconstruction assumes every canonical stage CAN appear in a
    trace)."""
    from petastorm_tpu.analysis.contracts import STAGES
    recording_calls = ('span', 'timed', 'record_complete',
                       'record_instant')
    recorded = set()
    for rel, source in _package_sources():
        for node in ast.walk(ast.parse(source, filename=rel)):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if _call_name(node) not in recording_calls:
                continue
            first = node.args[0]
            if isinstance(first, ast.Constant) and \
                    isinstance(first.value, str):
                recorded.add(first.value)
    missing = [stage for stage in STAGES if stage not in recorded]
    assert not missing, \
        'canonical stages never recorded by any span/trace call ' \
        '(dead contract entries, or instrumentation lost): %s' % missing


def test_exported_metric_names_are_documented():
    """Metric-name chain of custody, hubbed on analysis/contracts.py:
    every ``petastorm_tpu_*`` literal in the package is a member of
    contracts.METRIC_NAMES (no off-contract series can exist in source),
    and every member of METRIC_NAMES has a row in docs/telemetry.md's
    metric reference — dashboards are built from the docs, and an
    undocumented series is invisible operational surface."""
    from petastorm_tpu.analysis.contracts import METRIC_NAMES
    name_re = re.compile(r'petastorm_tpu_[a-z0-9_]*[a-z0-9]')
    with open(os.path.join(REPO, 'docs', 'telemetry.md')) as f:
        # extract WHOLE documented names with the same lexer — substring
        # containment would let an undocumented 'petastorm_tpu_cache_hits'
        # hide inside the documented '..._cache_hits_total'
        documented = set(name_re.findall(f.read()))
    names = set()
    for rel, source in _package_sources():
        for node in ast.walk(ast.parse(source, filename=rel)):
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str) and \
                    name_re.fullmatch(node.value):
                names.add(node.value)
    assert len(names) >= 10, 'metric-literal scan went blind: %s' % names
    off_contract = sorted(names - METRIC_NAMES)
    assert not off_contract, \
        'metric literals missing from contracts.METRIC_NAMES: %s' \
        % off_contract
    undocumented = sorted(METRIC_NAMES - documented)
    assert not undocumented, \
        'canonical metric names missing from docs/telemetry.md: %s' \
        % undocumented


def test_anomaly_kinds_are_canonical_and_documented():
    """Anomaly-event chain of custody, hubbed on analysis/contracts.py:
    every literal kind the package passes to ``record_anomaly`` (or a
    detector's ``_fire``/``_emit``) is a member of contracts.ANOMALY_KINDS;
    every canonical kind has a row in docs/telemetry.md's anomaly table;
    and every runbook heading a kind names is a real ``##`` section of
    docs/troubleshoot.md — an event can never point an operator at a
    runbook that does not exist."""
    from petastorm_tpu.analysis.contracts import ANOMALY_KINDS
    emitting_calls = ('record_anomaly', '_fire', '_emit')
    offenders = []
    emitted = set()
    for rel, source in _package_sources():
        for node in ast.walk(ast.parse(source, filename=rel)):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if _call_name(node) not in emitting_calls:
                continue
            first = node.args[0]
            if isinstance(first, ast.Constant) and \
                    isinstance(first.value, str):
                emitted.add(first.value)
                if first.value not in ANOMALY_KINDS:
                    offenders.append('%s:%d: %r' % (rel, node.lineno,
                                                    first.value))
    assert not offenders, \
        'anomaly kinds missing from contracts.ANOMALY_KINDS: %s' % offenders
    assert emitted >= set(ANOMALY_KINDS), \
        'canonical kinds never emitted anywhere (dead contract entries): ' \
        '%s' % sorted(set(ANOMALY_KINDS) - emitted)
    with open(os.path.join(REPO, 'docs', 'telemetry.md')) as f:
        telemetry_doc = f.read()
    undocumented = sorted(k for k in ANOMALY_KINDS
                          if '`%s`' % k not in telemetry_doc)
    assert not undocumented, \
        'anomaly kinds missing from docs/telemetry.md: %s' % undocumented
    with open(os.path.join(REPO, 'docs', 'troubleshoot.md')) as f:
        troubleshoot = f.read()
    missing = sorted(k for k, heading in ANOMALY_KINDS.items()
                     if '## %s' % heading not in troubleshoot)
    assert not missing, \
        'runbook headings missing from docs/troubleshoot.md for: %s' \
        % missing


def test_no_print_in_library_code():
    """Library modules log; only CLIs/examples/tools/benchmarks print."""
    allowed = ('tools', 'benchmark', 'etl%smetadata_util' % os.sep,
               'etl%spetastorm_generate_metadata' % os.sep, 'test_util',
               'analysis%s__main__' % os.sep)  # the pipecheck CLI reports
    offenders = []
    for path in SOURCES:
        rel = os.path.relpath(path, REPO)
        if not rel.startswith('petastorm_tpu'):
            continue
        if any(a in rel for a in allowed):
            continue
        tree = ast.parse(_read(path), filename=path)
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == 'print'):
                offenders.append('%s:%d' % (rel, node.lineno))
    assert not offenders, 'print() in library code: %s' % offenders
