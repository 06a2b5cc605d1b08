"""gltlint suite tests: every rule against its fixture corpus (one
true-positive and one true-negative file per rule), the suppression /
baseline machinery, the typed env-knob helper the rules enforce, and
the CI gate itself (nonzero on a seeded violation, zero on the tree)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
  sys.path.insert(0, REPO)

from tools.gltlint.core import (  # noqa: E402
    all_rules, lint_paths, load_baseline, write_baseline,
)

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'gltlint_fixtures')

#: rule -> (true-positive file, true-negative file), fixture-relative.
#: GLT001/GLT007/GLT008 are path-scoped, so their fixtures sit under a
#: miniature glt_tpu/ tree and lint with root=FIXTURES.
CASES = {
    'GLT001': ('glt_tpu/glt001_tp.py', 'glt_tpu/glt001_tn.py'),
    'GLT002': ('glt002_tp.py', 'glt002_tn.py'),
    'GLT003': ('glt003_tp.py', 'glt003_tn.py'),
    'GLT004': ('glt004_tp.py', 'glt004_tn.py'),
    'GLT005': ('glt005_tp.py', 'glt005_tn.py'),
    'GLT006': ('glt006_tp.py', 'glt006_tn.py'),
    'GLT007': ('glt_tpu/glt007_tp.py', 'glt_tpu/glt007_tn.py'),
    'GLT008': ('glt_tpu/ops/glt008_tp.py', 'glt_tpu/ops/glt008_tn.py'),
}

#: minimum finding count the true-positive file must produce (each
#: fixture seeds several distinct violation flavors)
MIN_TP = {
    'GLT001': 4, 'GLT002': 3, 'GLT003': 3, 'GLT004': 3,
    'GLT005': 4, 'GLT006': 2, 'GLT007': 5, 'GLT008': 3,
}


def _lint(relpath, code):
  result = lint_paths([os.path.join(FIXTURES, relpath)],
                      root=FIXTURES, select={code})
  assert not result.errors, result.errors
  return result.findings


@pytest.mark.parametrize('code', sorted(CASES))
def test_rule_true_positives(code):
  tp, _ = CASES[code]
  findings = _lint(tp, code)
  assert len(findings) >= MIN_TP[code], (
      f'{code} missed seeded violations in {tp}: '
      f'{[f.render() for f in findings]}')
  assert all(f.rule == code for f in findings)
  for f in findings:
    assert f.line > 0 and f.message and f.key.startswith(f'{code}::')


@pytest.mark.parametrize('code', sorted(CASES))
def test_rule_true_negatives(code):
  _, tn = CASES[code]
  findings = _lint(tn, code)
  assert findings == [], (
      f'{code} false positives in {tn}: '
      f'{[f.render() for f in findings]}')


def test_all_eight_rules_registered():
  codes = set()
  for rule in all_rules():
    codes.update(getattr(rule, 'codes', None) or (rule.code,))
  assert codes == {f'GLT00{i}' for i in range(1, 9)}


def test_inline_suppression_and_file_disable(tmp_path):
  src = tmp_path / 'mod.py'
  src.write_text(
      'def resolve(fut, v):\n'
      '  fut.set_result(v)  # gltlint: disable=GLT005\n'
      'def resolve2(fut, v):\n'
      '  # gltlint: disable-next=GLT005\n'
      '  fut.set_result(v)\n'
      'def resolve3(fut, v):\n'
      '  fut.set_result(v)\n')
  findings = lint_paths([str(src)], root=str(tmp_path),
                        select={'GLT005'}).findings
  assert len(findings) == 1 and findings[0].scope == 'resolve3'
  src.write_text('# gltlint: disable-file=GLT005\n' + src.read_text())
  assert lint_paths([str(src)], root=str(tmp_path),
                    select={'GLT005'}).findings == []


def test_baseline_roundtrip(tmp_path):
  src = tmp_path / 'mod.py'
  src.write_text('def f(fut):\n  fut.set_result(1)\n')
  result = lint_paths([str(src)], root=str(tmp_path), select={'GLT005'})
  assert len(result.findings) == 1
  bl = tmp_path / 'baseline.json'
  write_baseline(str(bl), result.findings)
  result2 = lint_paths([str(src)], root=str(tmp_path),
                       select={'GLT005'},
                       baseline=load_baseline(str(bl)))
  assert result2.findings == [] and len(result2.baselined) == 1
  assert result2.ok
  # baseline keys are line-free: shifting the code down two lines
  # must not invalidate the entry
  src.write_text('\n\n' + src.read_text())
  result3 = lint_paths([str(src)], root=str(tmp_path),
                       select={'GLT005'},
                       baseline=load_baseline(str(bl)))
  assert result3.findings == [] and len(result3.baselined) == 1


# -- the CI gate itself ---------------------------------------------------

def _run_cli(args, cwd=REPO):
  return subprocess.run(
      [sys.executable, '-m', 'tools.gltlint', *args],
      cwd=cwd, capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize('code', sorted(CASES))
def test_cli_gate_fails_on_seeded_violation(code):
  """Acceptance contract: the gate exits nonzero on EVERY rule's
  seeded fixture violation (root= the fixture mini-repo so the
  path-scoped rules resolve)."""
  tp, _ = CASES[code]
  proc = _run_cli([os.path.join(FIXTURES, tp), '--no-baseline',
                   '--root', FIXTURES, '--select', code])
  assert proc.returncode == 1, proc.stdout + proc.stderr
  assert code in proc.stdout


def test_cli_gate_green_on_tree_and_writes_json(tmp_path):
  """The exact contract the ci.yml lint job enforces: zero unsuppressed
  findings over glt_tpu/ tools/ tests/ with the checked-in baseline,
  machine-readable findings JSON on the side."""
  out = tmp_path / 'findings.json'
  proc = _run_cli(['glt_tpu/', 'tools/', 'tests/',
                   '--json', str(out)])
  assert proc.returncode == 0, proc.stdout + proc.stderr
  payload = json.loads(out.read_text())
  assert payload['new'] == []
  assert isinstance(payload['baselined'], list)


def test_missing_path_fails_not_vacuously_green(tmp_path):
  result = lint_paths([str(tmp_path / 'no_such_dir')],
                      root=str(tmp_path))
  assert result.errors and not result.ok
  proc = _run_cli(['glt_tpuu/'])     # the typo'd-gate scenario
  assert proc.returncode == 1 and 'does not exist' in proc.stdout


def test_lint_paths_accepts_one_shot_iterator(tmp_path):
  src = tmp_path / 'mod.py'
  src.write_text('def f(fut):\n  fut.set_result(1)\n')
  result = lint_paths((p for p in [str(src)]), root=str(tmp_path),
                      select={'GLT005'})
  assert len(result.findings) == 1


def test_glt006_nested_closure_not_attributed_to_outer(tmp_path):
  src = tmp_path / 'mod.py'
  src.write_text(
      'import threading\n'
      'def target():\n'
      '  def callback():\n'
      '    try:\n'
      '      pass\n'
      '    except Exception:\n'
      '      pass\n'                  # in the closure, not the target
      '  register(callback)\n'
      'threading.Thread(target=target).start()\n')
  assert lint_paths([str(src)], root=str(tmp_path),
                    select={'GLT006'}).findings == []


def test_write_baseline_refuses_on_errors(tmp_path):
  bad = tmp_path / 'broken.py'
  bad.write_text('def f(:\n')
  proc = _run_cli([str(bad), '--write-baseline',
                   '--baseline', str(tmp_path / 'bl.json')])
  assert proc.returncode == 1
  assert not (tmp_path / 'bl.json').exists()


def test_write_baseline_refuses_partial_rule_set(tmp_path):
  src = tmp_path / 'mod.py'
  src.write_text('def f(fut):\n  fut.set_result(1)\n')
  proc = _run_cli([str(src), '--select', 'GLT005', '--write-baseline',
                   '--baseline', str(tmp_path / 'bl.json')])
  assert proc.returncode == 2
  assert not (tmp_path / 'bl.json').exists()


def test_write_baseline_carries_out_of_scope_entries(tmp_path):
  """Rebaselining one subdirectory must not drop (or lose the
  justifications of) entries for files the run never looked at; TODO
  placeholders keep the exit nonzero until every entry is justified."""
  (tmp_path / 'a').mkdir()
  (tmp_path / 'b').mkdir()
  (tmp_path / 'a' / 'mod.py').write_text(
      'def f(fut):\n  fut.set_result(1)\n')
  (tmp_path / 'b' / 'mod.py').write_text(
      'def g(fut):\n  fut.set_result(2)\n')
  bl = tmp_path / 'bl.json'
  proc = _run_cli([str(tmp_path / 'a'), str(tmp_path / 'b'),
                   '--root', str(tmp_path), '--baseline', str(bl),
                   '--write-baseline'])
  # written, but nonzero: both fresh entries carry the TODO placeholder
  assert proc.returncode == 1, proc.stdout
  assert 'NEEDS JUSTIFICATION' in proc.stdout
  full = load_baseline(str(bl))
  assert len(full) == 2
  # hand-justify everything, then rebaseline only a/: b/'s entry (and
  # its justification) must survive untouched, and the exit goes green
  write_baseline(str(bl), [], carry={
      k: f'verified benign: single resolver ({k.split("::")[1]})'
      for k in full})
  proc = _run_cli([str(tmp_path / 'a'), '--root', str(tmp_path),
                   '--baseline', str(bl), '--write-baseline'])
  assert proc.returncode == 0, proc.stdout
  after = load_baseline(str(bl))
  assert len(after) == 2
  b_key = next(k for k in after if '::b/' in k)
  assert after[b_key] == 'verified benign: single resolver (b/mod.py)'


def test_write_baseline_still_writes_json(tmp_path):
  src = tmp_path / 'mod.py'
  src.write_text('def f(fut):\n  fut.set_result(1)\n')
  out = tmp_path / 'findings.json'
  proc = _run_cli([str(src), '--baseline', str(tmp_path / 'bl.json'),
                   '--write-baseline', '--json', str(out)])
  # exit 1 (fresh TODO entry), but the JSON artifact is still written
  assert proc.returncode == 1, proc.stdout
  assert json.loads(out.read_text())['new']


def test_cli_list_rules():
  proc = _run_cli(['--list-rules'])
  assert proc.returncode == 0
  for code in CASES:
    assert code in proc.stdout


# -- the env-knob helper GLT001 enforces ----------------------------------

def test_knob_types_and_malformed_defaults(monkeypatch):
  from glt_tpu.utils import env

  monkeypatch.setenv('GLT_T_INT', '12')
  assert env.knob('GLT_T_INT', 7) == 12
  monkeypatch.setenv('GLT_T_INT', 'zillion')
  with pytest.warns(RuntimeWarning, match='GLT_T_INT'):
    assert env.knob('GLT_T_INT', 7) == 7       # the import-crash class

  monkeypatch.setenv('GLT_T_FLOAT', '0.5')
  assert env.knob('GLT_T_FLOAT', 0.0) == 0.5

  for raw_val, want in (('1', True), ('true', True), ('on', True),
                        ('0', False), ('false', False), ('off', False)):
    monkeypatch.setenv('GLT_T_BOOL', raw_val)
    assert env.knob('GLT_T_BOOL', not want) is want
  monkeypatch.setenv('GLT_T_BOOL', 'maybe')
  with pytest.warns(RuntimeWarning):
    assert env.knob('GLT_T_BOOL', True) is True

  monkeypatch.setenv('GLT_T_STR', 'sort_fused')
  assert env.knob('GLT_T_STR', 'auto') == 'sort_fused'
  monkeypatch.delenv('GLT_T_STR')
  assert env.knob('GLT_T_STR', 'auto') == 'auto'
  monkeypatch.setenv('GLT_T_STR', '')
  assert env.knob('GLT_T_STR', 'auto') == 'auto'   # empty = unset
  assert env.knob('GLT_T_UNSET', None) is None

  monkeypatch.setenv('GLT_T_RAW', 'cpu')
  assert env.raw('GLT_T_RAW') == 'cpu'
  assert env.raw('GLT_T_RAW_UNSET', 'dflt') == 'dflt'


def test_knob_custom_parse_and_warn_once(monkeypatch):
  from glt_tpu.utils import env

  monkeypatch.setenv('GLT_T_LIST', '1,2,3')
  parse = lambda s: [int(x) for x in s.split(',')]  # noqa: E731
  assert env.knob('GLT_T_LIST', [], parse) == [1, 2, 3]
  monkeypatch.setenv('GLT_T_LIST', '1,x')
  with pytest.warns(RuntimeWarning):
    assert env.knob('GLT_T_LIST', [7], parse) == [7]
  # second read of the SAME bad value stays silent (hot loops)
  import warnings as _w
  with _w.catch_warnings():
    _w.simplefilter('error')
    assert env.knob('GLT_T_LIST', [7], parse) == [7]
