"""Tier-1 static-analysis gates.

Two layers, cheapest first:

1. ``test_parse_all`` — byte-compile every first-party ``.py`` under the
   running interpreter (3.10 semantics in CI). The seed shipped an
   f-string-backslash SyntaxError in metrics.py that took ~300 tests
   down with it at collection time; this gate turns that whole failure
   class into ONE named test with the offending file in the message.

2. ``test_lint_gate`` — run graftlint (rules R0–R6, see docs/lint.md)
   over ``kubernetes_tpu/ scripts/ tests/`` and fail on any finding not
   grandfathered in the committed ``.graftlint-baseline.json``. The
   merged tree lints clean, so the baseline is empty — any new finding
   is a regression and names its rule, file and line here.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: every first-party python root, plus the top-level entry scripts
#: (chip_smoke.py runs only on the chip — a SyntaxError there would
#: surface nowhere else)
PARSE_ROOTS = ("kubernetes_tpu", "scripts", "tests")
PARSE_FILES = ("bench.py", "__graft_entry__.py", "chip_smoke.py")

#: what the lint gate enforces (the acceptance surface of the linter CLI:
#: ``python -m kubernetes_tpu.lint kubernetes_tpu/ scripts/ tests/``)
LINT_PATHS = ("kubernetes_tpu", "scripts", "tests")

BASELINE = os.path.join(REPO_ROOT, ".graftlint-baseline.json")


def _first_party_files(roots=PARSE_ROOTS, files=PARSE_FILES):
    out = []
    for root in roots:
        top = os.path.join(REPO_ROOT, root)
        if not os.path.isdir(top):
            continue
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = [d for d in dirnames
                           if d != "__pycache__" and not d.startswith(".")]
            out.extend(os.path.join(dirpath, f)
                       for f in sorted(filenames) if f.endswith(".py"))
    for f in files:
        p = os.path.join(REPO_ROOT, f)
        if os.path.exists(p):
            out.append(p)
    return sorted(out)


def test_parse_all():
    """Every first-party file byte-compiles under this interpreter."""
    files = _first_party_files()
    assert len(files) > 100, f"suspiciously few files found: {len(files)}"
    failures = []
    for path in files:
        with open(path, "r", encoding="utf-8", errors="replace") as fh:
            src = fh.read()
        try:
            compile(src, path, "exec")
        except SyntaxError as e:
            rel = os.path.relpath(path, REPO_ROOT)
            failures.append(f"{rel}:{e.lineno}: {e.msg}")
    assert not failures, (
        "first-party files failed to byte-compile (the seed-breaking "
        "failure class):\n" + "\n".join(failures)
    )


def test_lint_gate():
    """graftlint exits clean over the enforced tree (baseline-aware) —
    the tier-1 wiring of ``python -m kubernetes_tpu.lint --format json``."""
    import json

    from kubernetes_tpu.lint import load_baseline, run_lint, subtract_baseline
    from kubernetes_tpu.lint.report import render_json, render_text

    paths = [os.path.join(REPO_ROOT, p) for p in LINT_PATHS]
    findings = run_lint(paths, root=REPO_ROOT)
    baselined = 0
    if os.path.exists(BASELINE):
        findings, baselined = subtract_baseline(findings, load_baseline(BASELINE))
    # machine-readable wiring stays honest: the JSON payload must parse
    # and agree with the finding list the human output renders
    payload = json.loads(render_json(findings, baselined))
    assert payload["baselined"] == baselined
    assert len(payload["findings"]) == len(findings)
    assert not findings, (
        "graftlint found non-baselined findings — fix them or add a "
        "justified inline suppression (docs/lint.md):\n"
        + render_text(findings, baselined)
    )


def test_lint_cli_json_exit_codes(tmp_path):
    """The CLI contract the docs promise: exit 0 + empty findings on a
    clean file, exit 1 + populated JSON on a dirty one."""
    import json
    import subprocess
    import sys

    clean = tmp_path / "clean.py"
    clean.write_text("import time\n\nSTAMP = time.monotonic\n")
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import time\n\ndef f():\n    return time.time()\n")

    def run(target):
        return subprocess.run(
            [sys.executable, "-m", "kubernetes_tpu.lint", str(target),
             "--format", "json", "--no-baseline", "--root", str(tmp_path)],
            capture_output=True, text=True, cwd=REPO_ROOT,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )

    ok = run(clean)
    assert ok.returncode == 0, ok.stderr
    assert json.loads(ok.stdout)["findings"] == []

    bad = run(dirty)
    assert bad.returncode == 1, bad.stderr
    payload = json.loads(bad.stdout)
    assert payload["counts"].get("R4") == 1
    f = payload["findings"][0]
    assert f["rule"] == "R4" and f["path"] == "dirty.py" and f["line"] == 4

    # a typo'd explicit path is a usage error (exit 2), NOT a clean run —
    # otherwise a misspelled path in CI becomes a permanent false pass
    typo = run(tmp_path / "no_such_dir")
    assert typo.returncode == 2, (typo.stdout, typo.stderr)
    assert "do not exist" in typo.stderr


def test_lint_cli_unknown_select_rule_is_usage_error(tmp_path):
    """``--select`` with a rule id the engine doesn't know is a usage
    error (exit 2) naming the known rules — a typo like ``--select R01``
    in CI must fail loudly, not silently lint nothing and pass."""
    import subprocess
    import sys

    from kubernetes_tpu.lint.engine import RULE_IDS

    target = tmp_path / "ok.py"
    target.write_text("X = 1\n")

    bad = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.lint", str(target),
         "--select", "R9,R99", "--no-baseline", "--root", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert bad.returncode == 2, (bad.stdout, bad.stderr)
    assert "unknown rule id" in bad.stderr and "R99" in bad.stderr
    # the error message must enumerate the valid universe so the fix is
    # one glance away
    for rule in RULE_IDS:
        assert rule in bad.stderr
