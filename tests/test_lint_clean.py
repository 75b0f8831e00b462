"""Tier-1 gate: ``tpurun-lint`` over ``dlrover_tpu/`` is CLEAN.

The whole point of the suite (docs/analysis.md): the invariants PRs 1-4
paid for are machine-enforced from PR 6 forward. Pure AST — no jax
import — so this runs in milliseconds anywhere.
"""

import json
import os

from dlrover_tpu.analysis import Baseline, run_lint
from dlrover_tpu.analysis.cli import DEFAULT_BASELINE, main as lint_main

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = os.path.join(_REPO, "dlrover_tpu")

# The full-repo AST scan costs seconds on a loaded tier-1 box; every
# in-process test below asserts against the SAME run — one scan, not
# one per test (the CLI test keeps its own invocation for the main()
# wiring, scoped to a subpackage).
_SHARED = {}


def _repo_lint_result():
    if "result" not in _SHARED:
        baseline = (
            Baseline.load(DEFAULT_BASELINE)
            if os.path.exists(DEFAULT_BASELINE)
            else None
        )
        _SHARED["result"] = run_lint(
            [_PKG], baseline=baseline, repo_root=_REPO
        )
    return _SHARED["result"]


def test_repo_is_lint_clean():
    result = _repo_lint_result()
    assert result.clean, "tpurun-lint is not clean:\n" + "\n".join(
        [v.render() for v in result.violations]
        + result.errors
        + [f"stale baseline entry: {e.key()}" for e in result.stale_baseline]
    )


def test_cli_exits_zero_and_reports(capsys):
    """main() wiring: exit status + the summary line. Scoped to the
    analysis package — full-repo cleanliness is already asserted by
    test_repo_is_lint_clean against the same engine and baseline."""
    assert lint_main([os.path.join(_PKG, "analysis")]) == 0
    out = capsys.readouterr().out
    assert "0 violations" in out


def test_every_suppression_carries_a_reason():
    """Redundant with run_lint's own error channel, but kept explicit:
    the reasons ARE the documentation of every intentional exception."""
    result = _repo_lint_result()
    for v, s in result.suppressed:
        assert s.reason.strip(), f"bare suppression at {v.path}:{s.line}"


def test_checked_in_baseline_is_empty_or_reasoned():
    data = json.load(open(DEFAULT_BASELINE))
    for entry in data["entries"]:
        assert entry.get("reason", "").strip(), entry
    # PR 6 fixed everything it found; keep the count pinned so additions
    # are a conscious choice (update docs/analysis.md when this moves)
    assert len(data["entries"]) == 0


def test_console_script_registered():
    pyproject = open(os.path.join(_REPO, "pyproject.toml")).read()
    assert 'tpurun-lint = "dlrover_tpu.analysis.cli:main"' in pyproject


def test_analysis_doc_linked():
    assert os.path.exists(os.path.join(_REPO, "docs", "analysis.md"))
    for rel in ("README.md", "docs/chaos.md"):
        text = open(os.path.join(_REPO, rel)).read()
        assert "analysis.md" in text, f"{rel} does not link docs/analysis.md"


def test_analysis_package_is_jax_free():
    """The suite must import (and run) without jax: no runtime module
    creep into the analysis package."""
    import sys
    import subprocess

    # linting the analysis package itself is enough to prove the
    # import graph is jax-free — the full-repo scan (same engine) runs
    # in-process above, and one per-test repeat of it costs real
    # seconds inside the tier-1 wall-clock budget
    code = (
        "import sys\n"
        "sys.modules['jax'] = None  # poison: any import attempt dies\n"
        "from dlrover_tpu.analysis import run_lint\n"
        "r = run_lint([r'%s'], repo_root=r'%s')\n"
        "sys.exit(0 if r is not None else 1)\n"
        % (os.path.join(_PKG, "analysis"), _REPO)
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=_REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr


def test_semantic_passes_are_jax_free_and_non_vacuous():
    """The v3 passes read jax-adjacent source (mesh registry, sharding
    rules, spec literals) but must do it by AST: with jax poisoned they
    still load the real tables AND still fire on their fixtures — the
    poison must not degrade them into silent no-ops."""
    import sys
    import subprocess

    fx = os.path.join(_REPO, "tests", "lint_fixtures")
    code = (
        "import sys\n"
        "sys.modules['jax'] = None  # poison: any import attempt dies\n"
        "from dlrover_tpu.analysis import run_lint\n"
        "from dlrover_tpu.analysis.passes import (\n"
        "    epoch_fence, journal_conformance, mesh_axes, reshard_coverage)\n"
        "from dlrover_tpu.analysis.passes.mesh_axes import load_axis_registry\n"
        "from dlrover_tpu.analysis.passes.reshard_coverage import load_tables\n"
        "import os\n"
        "registry, axes, err = load_axis_registry(\n"
        "    os.path.join(r'%(repo)s', 'dlrover_tpu', 'parallel', 'mesh.py'))\n"
        "assert registry and not err, err\n"
        "rules, policies, elastic = load_tables(r'%(repo)s')\n"
        "assert rules and policies and elastic\n"
        "for pass_mod, fixture, needle in [\n"
        "    (mesh_axes, 'fx_mesh_axes.py', 'zz_bogus'),\n"
        "    (reshard_coverage, 'fx_reshard_coverage.py', 'zz_lora'),\n"
        "    (journal_conformance, 'fx_journal_conformance.py', 'fx.sett'),\n"
        "    (epoch_fence, 'fx_epoch_fence.py', 'master_epoch'),\n"
        "]:\n"
        "    r = run_lint([os.path.join(r'%(fx)s', fixture)],\n"
        "                 passes=[pass_mod], repo_root=r'%(repo)s')\n"
        "    assert any(needle in v.message for v in r.violations), (\n"
        "        fixture, [v.render() for v in r.violations])\n"
        "r = run_lint([r'%(pkg)s'],\n"
        "             passes=[mesh_axes, reshard_coverage,\n"
        "                     journal_conformance, epoch_fence],\n"
        "             repo_root=r'%(repo)s')\n"
        "assert not r.violations, [v.render() for v in r.violations]\n"
        "assert r.suppressed  # node_check probe-axis suppressions seen\n"
    ) % {"repo": _REPO, "pkg": _PKG, "fx": fx}
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=_REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr


def test_lock_witness_is_jax_free():
    """The runtime sanitizer must install and witness locks with jax
    poisoned — it runs inside arbitrary runtime processes, including
    ones that must never import jax (the agent, the lint CI image)."""
    import sys
    import subprocess

    code = (
        "import sys, threading, types\n"
        "sys.modules['jax'] = None  # poison: any import attempt dies\n"
        "from dlrover_tpu.analysis import witness\n"
        "witness.install()\n"
        "mod = types.ModuleType('dlrover_tpu._poison_probe')\n"
        "sys.modules[mod.__name__] = mod\n"
        "src = ('import threading\\n'\n"
        "       'def make():\\n'\n"
        "       '    a = threading.Lock()\\n'\n"
        "       '    b = threading.Lock()\\n'\n"
        "       '    return a, b\\n')\n"
        "exec(compile(src, 'probe.py', 'exec'), mod.__dict__)\n"
        "a, b = mod.make()\n"
        "assert type(a).__name__ == '_WitnessLock', type(a)\n"
        "with a:\n"
        "    with b:\n"
        "        pass\n"
        "s = witness.stats()\n"
        "assert s['edges'] == 1 and not s['inversions'], s\n"
        "witness.uninstall()\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=_REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr


def test_no_run_products_tracked():
    """Repo hygiene: what a run makes is git-ignored, never tracked —
    chip_smoke.py's work dir, the persistent compile cache, the native
    build products and what the chip tool brings back. The chip check
    copies only what git would
    commit, so a tracked product would also ride to the chip in place
    of a build from the committed sources."""
    import re
    import subprocess

    proc = subprocess.run(
        ["git", "ls-files"],
        cwd=_REPO,
        capture_output=True,
        text=True,
        timeout=60,
    )
    if proc.returncode != 0:
        import pytest

        pytest.skip("not a git checkout")
    product = re.compile(
        r"^(\.smoke_work/|\.jax_compile_cache/|chiprun_out/"
        r"|native/.*\.so$|native/.*/test_(driver|tpu_timer|tsan)"
        r"(_tsan)?$)"
    )
    offenders = [f for f in proc.stdout.splitlines() if product.match(f)]
    assert not offenders, (
        "run products tracked (add to .gitignore, git rm --cached): "
        f"{offenders}"
    )
