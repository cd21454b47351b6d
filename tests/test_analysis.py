"""pipecheck gate + self-tests.

Two halves: (1) the baseline-zero gate — every analyzer pass over the
whole ``petastorm_tpu`` package yields no findings, so a contract
regression (raw env read, typo'd stage, blocking call under a lock,
leaky thread, closure payload) fails tier-1 at commit time; (2) rule
self-tests — the known-bad fixtures under ``tests/data/analysis/``
prove each rule actually fires, so the gate can never rot into a
scanner that silently matches nothing.
"""

import os
import subprocess
import sys

import pytest

from petastorm_tpu.analysis import (
    ALL_RULES, RULE_DESCRIPTIONS, analyze_paths, analyze_source, contracts,
)
from petastorm_tpu.analysis.core import iter_python_files
from petastorm_tpu.analysis.pass_env_knobs import check_docs_coverage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, 'petastorm_tpu')
FIXTURES = os.path.join(REPO, 'tests', 'data', 'analysis')


def _fixture_findings(name, rule=None):
    path = os.path.join(FIXTURES, name)
    findings = analyze_paths([path], root=REPO, check_docs=False)
    if rule is not None:
        findings = [f for f in findings if f.rule == rule]
    return findings


# -- the gate -----------------------------------------------------------------


def test_package_is_finding_free():
    """The whole package passes every pass — the CI gate in test form."""
    findings = analyze_paths([PACKAGE], root=REPO)
    assert not findings, 'pipecheck findings on the tree:\n%s' \
        % '\n'.join(str(f) for f in findings)


def test_gate_scans_the_real_tree():
    """Guard against a silently-empty scan (wrong path, glob rot)."""
    files = list(iter_python_files([PACKAGE]))
    assert len(files) > 50
    assert any(f.endswith('dispatcher.py') for f in files)


def test_registered_knobs_are_documented():
    findings = check_docs_coverage(os.path.join(REPO, 'docs',
                                                'env_knobs.md'))
    assert not findings, '\n'.join(str(f) for f in findings)


def test_every_rule_has_a_description():
    assert set(ALL_RULES) == set(RULE_DESCRIPTIONS)
    assert len(ALL_RULES) == 9


# -- rule self-tests over the fixtures ---------------------------------------


def test_env_knob_rule_fires():
    findings = _fixture_findings('bad_env_knob.py', 'env-knob')
    lines = [f.line for f in findings]
    assert lines == [8, 11, 14, 17, 20], findings
    assert 'unregistered knob' in findings[-1].message


def test_canonical_name_rule_fires():
    findings = _fixture_findings('bad_canonical_name.py', 'canonical-name')
    assert [f.line for f in findings] == [11, 15, 16], findings
    # the metric finding resolved through a module-level constant
    assert 'petastorm_tpu_reventilated_totl' in findings[2].message


def test_faultpoint_rule_fires():
    """Every fault_hit() call site must name a registered faultpoint —
    literal or resolved through a module constant; the registered site
    at the fixture's tail stays clean."""
    findings = _fixture_findings('bad_faultpoint.py', 'faultpoint')
    assert [f.line for f in findings] == [9, 11], findings
    assert 'io.reed' in findings[0].message
    assert 'contracts.FAULTPOINTS' in findings[0].message
    assert 'decode.rowgrup' in findings[1].message


def test_blocking_under_lock_rule_fires():
    findings = _fixture_findings('bad_blocking_under_lock.py',
                                 'blocking-under-lock')
    lines = [f.line for f in findings]
    # 7 hazards in drain(), 1 in acquire_style(); the bounded/unlocked
    # calls in drain_politely() and after release() stay clean
    assert lines == [17, 18, 19, 20, 21, 22, 23, 34], findings


def test_lock_order_rule_fires():
    findings = _fixture_findings('bad_lock_order.py', 'lock-order')
    assert len(findings) == 1, findings
    assert '_IO_LOCK' in findings[0].message
    assert '_STATE_LOCK' in findings[0].message


def test_thread_lifecycle_rule_fires():
    findings = _fixture_findings('bad_thread_lifecycle.py',
                                 'thread-lifecycle')
    assert [f.line for f in findings] == [9, 31], findings


def test_pickle_payload_rule_fires():
    findings = _fixture_findings('bad_pickle_payload.py', 'pickle-payload')
    assert [f.line for f in findings] == [10, 11, 12], findings


def test_buffer_escape_rule_fires():
    findings = _fixture_findings('bad_buffer_escape.py', 'buffer-escape')
    # object state, queue, closure, return, astype alias, whole-program
    # propagation through give_back(); the owned/annotated/killed-taint
    # functions at the fixture's tail stay clean
    assert [f.line for f in findings] == [11, 15, 20, 25, 36, 41], findings
    assert 'give_back()' in findings[-1].message


def test_buffer_write_rule_fires():
    findings = _fixture_findings('bad_buffer_escape.py', 'buffer-write')
    assert [f.line for f in findings] == [30, 31, 32], findings
    assert 'copyto' in findings[2].message


def test_owns_annotation_silences_buffer_findings():
    findings = analyze_source(
        "import numpy as np\n"
        "def f(buf):\n"
        "    view = np.frombuffer(buf, dtype=np.uint8)\n"
        "    return view  # pipesan: owns\n")
    assert findings == []


def test_fresh_temporary_views_are_owned_by_construction():
    # frombuffer over a call expression: the anonymous temporary's only
    # reference becomes the array's .base — owned, not borrowed
    assert analyze_source(
        "import numpy as np\n"
        "def f(payload):\n"
        "    return np.frombuffer(bytes(payload), dtype=np.uint8)\n") == []


def test_comprehensions_respect_laundering_and_unpack_is_elementwise():
    """[v.copy() for v in views] (the documented fix) and a literal
    tuple unpack assigning a fresh copy next to a tainted value are both
    clean; a comprehension carrying the raw views still taints."""
    assert analyze_source(
        "import numpy as np\n"
        "def f(frames):\n"
        "    views = [np.frombuffer(b) for b in frames]\n"
        "    return [v.copy() for v in views]\n"
        "def g(frames):\n"
        "    views = [np.frombuffer(b) for b in frames]\n"
        "    return [len(v) for v in views]\n"
        "def h(buf):\n"
        "    view = np.frombuffer(buf, dtype=np.uint8)\n"
        "    size, owned = view.nbytes, view.copy()\n"
        "    return owned\n"
        "def k(buf):\n"
        "    view = np.frombuffer(buf, dtype=np.uint8)\n"
        "    return view.shape[0] * view.itemsize\n") == []
    tainted = analyze_source(
        "import numpy as np\n"
        "def f(frames):\n"
        "    return [np.frombuffer(b) for b in frames]\n")
    assert [f.rule for f in tainted] == ['buffer-escape']


def test_recv_frames_list_mutation_is_not_a_buffer_write():
    """recv_multipart returns a caller-owned LIST; replacing/extending
    its elements mutates the list, not the borrowed frame memory."""
    assert analyze_source(
        "def f(sock, header):\n"
        "    frames = sock.recv_multipart(copy=False)\n"
        "    frames[0] = header\n"
        "    frames += [b'trailer']\n"
        "    return len(frames)\n") == []


def test_owning_methods_launder_taint():
    """view.copy() (and reductions/materializations) OWN their result —
    the canonical fix for an escape finding must itself be clean."""
    assert analyze_source(
        "import numpy as np\n"
        "def f(buf):\n"
        "    view = np.frombuffer(buf, dtype=np.uint8)\n"
        "    return view.copy()\n"
        "def g(buf):\n"
        "    view = np.frombuffer(buf, dtype=np.uint8)\n"
        "    return view.sum()\n") == []


def test_whole_program_lock_order_rule_fires():
    findings = _fixture_findings('bad_lock_order_global', 'lock-order')
    assert len(findings) == 1, findings
    assert 'whole-program' in findings[0].message
    assert '_A_LOCK' in findings[0].message
    assert '_FLUSH_LOCK' in findings[0].message


def test_whole_program_lock_order_resolves_imported_locks(tmp_path):
    """A lock IMPORTED from another module must globalize to its defining
    module, or the two sides of a cross-module inversion never compare
    equal (regression: false negative)."""
    (tmp_path / 'liba.py').write_text(
        "import threading\n"
        "from libb import FLUSH_LOCK\n"
        "A_LOCK = threading.Lock()\n"
        "def one():\n"
        "    with A_LOCK:\n"
        "        with FLUSH_LOCK:\n"
        "            pass\n")
    (tmp_path / 'libb.py').write_text(
        "import threading\n"
        "from liba import A_LOCK\n"
        "FLUSH_LOCK = threading.Lock()\n"
        "def two():\n"
        "    with FLUSH_LOCK:\n"
        "        with A_LOCK:\n"
        "            pass\n")
    findings = analyze_paths([str(tmp_path)], check_docs=False)
    locks = [f for f in findings if f.rule == 'lock-order']
    assert len(locks) == 1, findings
    assert 'A_LOCK' in locks[0].message
    assert 'FLUSH_LOCK' in locks[0].message


def test_whole_program_pass_defers_same_module_inversions():
    """An inversion whose both orders are lexical within one module is
    the per-module scan's report — run_project must not double-report it
    even when a call-graph witness for one order is recorded first."""
    findings = analyze_source(
        "import threading\n"
        "a_lock = threading.Lock()\n"
        "b_lock = threading.Lock()\n"
        "def helper():\n"
        "    with b_lock:\n"
        "        pass\n"
        "def f1():\n"
        "    with a_lock:\n"
        "        helper()\n"
        "def f2():\n"
        "    with a_lock:\n"
        "        with b_lock:\n"
        "            pass\n"
        "def f3():\n"
        "    with b_lock:\n"
        "        with a_lock:\n"
        "            pass\n", select=['lock-order'])
    assert len(findings) == 1, findings


def test_suppression_comment_silences_findings():
    assert _fixture_findings('suppressed.py') == []


def test_suppression_is_rule_specific():
    findings = analyze_source(
        "import queue\nimport threading\n_lock = threading.Lock()\n"
        "q = queue.Queue()\n"
        "def f():\n"
        "    with _lock:\n"
        "        q.get()  # pipecheck: disable=lock-order\n")
    assert [f.rule for f in findings] == ['blocking-under-lock']


# -- library/CLI surface ------------------------------------------------------


def test_analyze_source_on_clean_snippet():
    assert analyze_source('x = 1\n') == []


def test_select_narrows_rules():
    source = ("import os\nimport threading\n"
              "_RAW = os.environ.get('PETASTORM_TPU_STAGING_SLOTS')\n"
              "t = threading.Thread(target=print)\nt.start()\n")
    only_env = analyze_source(source, select=['env-knob'])
    assert [f.rule for f in only_env] == ['env-knob']
    both = analyze_source(source)
    assert {f.rule for f in both} == {'env-knob', 'thread-lifecycle'}


def test_findings_are_structured():
    findings = _fixture_findings('bad_lock_order.py')
    record = findings[0].as_dict()
    assert set(record) == {'path', 'line', 'rule', 'message'}
    assert str(findings[0]).startswith(record['path'])


def test_missing_path_raises_not_clean():
    """A scan of nothing must never read as a clean pass (a wrong cwd or
    a renamed package would otherwise turn the CI gate silently green)."""
    with pytest.raises(FileNotFoundError):
        analyze_paths(['no_such_dir_xyz'])


def test_empty_scan_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match='no Python files'):
        analyze_paths([str(tmp_path)])


def test_contracts_import_is_light():
    """telemetry's production import path (analysis.contracts via the
    knob registry) must not drag the ast/tokenize analyzer into every
    reader/worker process."""
    proc = subprocess.run(
        [sys.executable, '-c',
         'import sys; import petastorm_tpu.telemetry.knobs; '
         'bad = [m for m in sys.modules if "analysis.core" in m or '
         '"analysis.pass_" in m or "analysis.findings" in m]; '
         'assert not bad, bad; print("light")'],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert 'light' in proc.stdout


@pytest.mark.parametrize('args,expected_rc', [
    (['petastorm_tpu'], 0),
    (['tests/data/analysis/bad_lock_order.py', '--no-docs-check'], 1),
    (['--list-rules'], 0),
    (['petastorm_tpu', '--select', 'no-such-rule'], 2),
    (['no_such_dir_xyz'], 2),
])
def test_cli_exit_codes(args, expected_rc):
    proc = subprocess.run([sys.executable, '-m', 'petastorm_tpu.analysis']
                          + args, cwd=REPO, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == expected_rc, (proc.stdout, proc.stderr)


def _run_cli(args, **kw):
    return subprocess.run([sys.executable, '-m', 'petastorm_tpu.analysis']
                          + args, cwd=REPO, capture_output=True, text=True,
                          timeout=120, **kw)


def test_cli_baseline_filters_known_findings(tmp_path):
    """--baseline lets a rule land strict-on-new-code: a --json dump of
    the current findings turns the same scan green."""
    fixture = 'tests/data/analysis/bad_buffer_escape.py'
    dump = _run_cli([fixture, '--json', '--no-docs-check'])
    assert dump.returncode == 1
    baseline = tmp_path / 'baseline.jsonl'
    baseline.write_text(dump.stdout)
    clean = _run_cli([fixture, '--baseline', str(baseline),
                      '--fail-on-new', '--no-docs-check'])
    assert clean.returncode == 0, (clean.stdout, clean.stderr)
    assert 'suppressed' in clean.stderr


def test_cli_baseline_still_fails_on_new_findings(tmp_path):
    dump = _run_cli(['tests/data/analysis/bad_lock_order.py', '--json',
                     '--no-docs-check'])
    baseline = tmp_path / 'baseline.jsonl'
    baseline.write_text(dump.stdout)
    mixed = _run_cli(['tests/data/analysis/bad_lock_order.py',
                      'tests/data/analysis/bad_buffer_escape.py',
                      '--baseline', str(baseline), '--no-docs-check'])
    assert mixed.returncode == 1
    # only the NEW findings survive the filter
    assert 'bad_lock_order.py' not in mixed.stdout
    assert 'bad_buffer_escape.py' in mixed.stdout


def test_cli_fail_on_new_requires_a_baseline():
    proc = _run_cli(['petastorm_tpu', '--fail-on-new'])
    assert proc.returncode == 2
    assert '--baseline' in proc.stderr


def test_cli_unusable_baseline_is_an_error(tmp_path):
    """A corrupt baseline must not silently waive every finding."""
    bogus = tmp_path / 'bogus.jsonl'
    bogus.write_text('not json\n')
    proc = _run_cli(['tests/data/analysis/bad_lock_order.py',
                     '--baseline', str(bogus), '--no-docs-check'])
    assert proc.returncode == 2
    assert 'unusable baseline' in proc.stderr


def test_cli_json_output():
    proc = subprocess.run(
        [sys.executable, '-m', 'petastorm_tpu.analysis',
         'tests/data/analysis/bad_lock_order.py', '--json',
         '--no-docs-check'],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    import json
    records = [json.loads(line) for line in proc.stdout.splitlines()]
    assert records and records[0]['rule'] == 'lock-order'


# -- contracts stay in sync with the runtime ---------------------------------


def test_contracts_are_the_runtime_sets():
    """telemetry imports the SAME objects the checker verifies against —
    the drift this PR exists to make impossible."""
    from petastorm_tpu import telemetry
    from petastorm_tpu.telemetry import tracing
    assert telemetry.STAGES is contracts.STAGES
    assert tracing.EVENT_NAMES is contracts.EVENT_NAMES
    from petastorm_tpu.telemetry.knobs import KNOWN_KNOBS
    assert KNOWN_KNOBS is contracts.KNOWN_KNOBS
