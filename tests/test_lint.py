"""tpurun-lint unit tests: every pass fires on its planted fixture,
both suppression forms work (same-line and line-above), bare ignores
are errors, and the baseline round-trips (stale entries reported).

The repo-wide zero-violation gate lives in tests/test_lint_clean.py;
this file exercises the machinery against tests/lint_fixtures/.
"""

import json
import os

import pytest

from dlrover_tpu.analysis import Baseline, run_lint
from dlrover_tpu.analysis.cli import main as lint_main
from dlrover_tpu.analysis.passes import (
    ALL_PASSES,
    PASS_BY_ID,
    blocking_under_lock,
    endpoint_conformance,
    env_knobs,
    epoch_fence,
    exception_swallow,
    host_sync,
    import_purity,
    injection_coverage,
    journal_conformance,
    lock_order,
    mesh_axes,
    reshard_coverage,
    rpc_deadline,
    thread_lifecycle,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FIXTURES = os.path.join(_REPO, "tests", "lint_fixtures")


def _fx(name):
    return os.path.join(_FIXTURES, name)


def _run(path, lint_pass):
    return run_lint([path], passes=[lint_pass], repo_root=_REPO)


class TestPassesFireOnFixtures:
    def test_import_purity_fires(self):
        r = _run(_fx("fx_import_purity.py"), import_purity)
        assert len(r.violations) == 1, r.violations
        v = r.violations[0]
        assert v.pass_id == "import-purity"
        assert "jax_compilation_cache_dir" in v.code
        # the suppressed twin (line-above form) and only it
        assert len(r.suppressed) == 1
        assert not r.errors

    def test_import_purity_main_guard_and_functions_exempt(self):
        r = _run(_fx("fx_import_purity.py"), import_purity)
        flagged_lines = {v.line for v in r.violations} | {
            v.line for v, _s in r.suppressed
        }
        src = open(_fx("fx_import_purity.py")).readlines()
        for i, text in enumerate(src, start=1):
            if "fine_inside_a_function" in text or "__main__" in text:
                assert i not in flagged_lines

    def test_blocking_under_lock_fires(self):
        r = _run(_fx("fx_blocking_under_lock.py"), blocking_under_lock)
        assert len(r.violations) == 1, r.violations
        assert "sleep" in r.violations[0].message
        # same-line suppression on the untimed join
        assert len(r.suppressed) == 1
        assert "join" in r.suppressed[0][0].message
        assert not r.errors

    def test_host_sync_fires_on_marker_and_jit(self):
        r = _run(_fx("fx_host_sync.py"), host_sync)
        msgs = [v.message for v in r.violations]
        assert any("float()" in m and "dispatch_round" in m for m in msgs)
        assert any(".item()" in m and "jitted_body" in m for m in msgs)
        assert len(r.violations) == 2, r.violations
        # the drain point is suppressed; the cold path is not hot
        assert len(r.suppressed) == 1
        assert "device_get" in r.suppressed[0][0].message

    def test_host_sync_flags_per_call_heavy_import(self, tmp_path):
        p = tmp_path / "fx.py"
        p.write_text(
            "# tpulint: hotpath\n"
            "def step(state):\n"
            "    import jax\n"
            "    return state\n"
        )
        r = _run(str(p), host_sync)
        assert len(r.violations) == 1
        assert "per-call import" in r.violations[0].message

    def test_rpc_deadline_fires(self):
        r = _run(_fx("fx_rpc_deadline.py"), rpc_deadline)
        assert len(r.violations) == 1, r.violations
        assert "hard-coded deadline" in r.violations[0].message
        # urlopen with NO deadline is also a violation — suppressed here
        assert len(r.suppressed) == 1
        assert "no deadline" in r.suppressed[0][0].message

    def test_env_knobs_fires_on_unregistered_access(self):
        r = _run(_fx("fx_env_knobs.py"), env_knobs)
        assert len(r.violations) == 1, r.violations
        assert "DLROVER_NOT_A_REGISTERED_KNOB" in r.violations[0].message
        assert len(r.suppressed) == 1

    def test_bare_ignore_is_an_error(self):
        r = _run(_fx("fx_bad_suppression.py"), blocking_under_lock)
        assert not r.violations  # the site IS suppressed...
        assert r.errors and "needs a reason" in r.errors[0]
        assert not r.clean  # ...but the bare ignore fails the run

    def test_lock_order_fires_through_call_edge(self):
        r = _run(_fx("fx_lock_order.py"), lock_order)
        assert len(r.violations) == 1, [v.render() for v in r.violations]
        v = r.violations[0]
        assert v.pass_id == "lock-order"
        assert v.code.startswith("cycle:")
        # one arm of the planted cycle goes through self._touch_ledger()
        assert "_step_lock" in v.message and "_ledger_lock" in v.message
        # the suppressed-twin cycle (journal/ring) and only it
        assert len(r.suppressed) == 1
        assert "_journal_lock" in r.suppressed[0][0].message
        assert not r.errors

    def test_thread_lifecycle_fires(self):
        r = _run(_fx("fx_thread_lifecycle.py"), thread_lifecycle)
        assert len(r.violations) == 1, [v.render() for v in r.violations]
        assert "_leaked" in r.violations[0].message
        # the suppressed twin is the handed-off Popen
        assert len(r.suppressed) == 1
        assert "Popen" in r.suppressed[0][0].message
        assert not r.errors

    def test_exception_swallow_fires(self):
        r = _run(_fx("fx_exception_swallow.py"), exception_swallow)
        assert len(r.violations) == 1, [v.render() for v in r.violations]
        assert "swallows" in r.violations[0].message
        assert len(r.suppressed) == 1
        assert not r.errors

    def test_endpoint_conformance_fires(self):
        r = _run(_fx("fx_endpoint_conformance.py"), endpoint_conformance)
        assert len(r.violations) == 1, [v.render() for v in r.violations]
        assert r.violations[0].code == "client:/fx/drifted"
        # the dead route is the suppressed twin; the exact and
        # under-prefix clients are conformant
        assert len(r.suppressed) == 1
        assert r.suppressed[0][0].code == "route:/fx/dead-route"
        assert not r.errors

    def test_mesh_axes_fires(self):
        r = _run(_fx("fx_mesh_axes.py"), mesh_axes)
        assert len(r.violations) == 1, [v.render() for v in r.violations]
        v = r.violations[0]
        assert v.pass_id == "mesh-axes" and "zz_bogus" in v.message
        # the suppressed twin; registered axes (batch/seq, shape["dp"])
        # are conformant
        assert len(r.suppressed) == 1
        assert "zz_experiment" in r.suppressed[0][0].message
        assert not r.errors

    def test_reshard_coverage_fires(self):
        r = _run(_fx("fx_reshard_coverage.py"), reshard_coverage)
        assert len(r.violations) == 1, [v.render() for v in r.violations]
        v = r.violations[0]
        assert v.pass_id == "reshard-coverage" and "zz_lora" in v.message
        # covered categories (params/opt_state) and the suppressed twin
        assert len(r.suppressed) == 1
        assert "zz_probe" in r.suppressed[0][0].message
        assert not r.errors

    def test_journal_conformance_fires(self):
        r = _run(_fx("fx_journal_conformance.py"), journal_conformance)
        codes = {v.code for v in r.violations}
        # the drifted record kind AND the dead replay branch
        assert codes == {"recorded:fx.sett", "applied:fx.ghost"}, [
            v.render() for v in r.violations
        ]
        # the one-way component is the suppressed twin
        assert len(r.suppressed) == 1
        assert r.suppressed[0][0].code == "pair:FxHalfComponent"
        assert not r.errors

    def test_epoch_fence_fires(self):
        r = _run(_fx("fx_epoch_fence.py"), epoch_fence)
        assert len(r.violations) == 2, [v.render() for v in r.violations]
        msgs = [v.message for v in r.violations]
        # the unstamped servicer response AND the raw transport client
        assert any("master_epoch" in m for m in msgs)
        assert any("bypasses the epoch fence" in m for m in msgs)
        assert len(r.suppressed) == 1
        assert not r.errors


class TestInjectionCoveragePass:
    def _tree(self, tmp_path, tests_text):
        faults = tmp_path / "faults.py"
        faults.write_text(
            "INJECTION_POINTS = {\n"
            '    "covered.point": "a point with a drill",\n'
            '    "uncovered.point": "a point nobody exercises",\n'
            "}\n"
        )
        scenarios = tmp_path / "scenarios.py"
        scenarios.write_text(
            "def drill(w=None):\n    return {}\n\n"
            'SCENARIOS = {"my_drill": drill, "dusty_drill": drill}\n'
        )
        tests = tmp_path / "tests"
        tests.mkdir()
        (tests / "test_x.py").write_text(tests_text)
        return str(faults), str(tests), str(scenarios)

    def test_uncovered_point_and_unexercised_scenario_flagged(
        self, tmp_path
    ):
        faults, tests, scenarios = self._tree(
            tmp_path, 'def test_a():\n    fire("covered.point")\n'
        )
        got = list(
            injection_coverage.check_coverage(
                faults, tests, scenarios_path=scenarios
            )
        )
        codes = {v.code for v in got}
        assert "uncovered.point" in codes
        assert "scenario:my_drill" in codes
        assert "scenario:dusty_drill" in codes
        assert "covered.point" not in codes

    def test_point_covered_through_exercised_scenario(self, tmp_path):
        faults, tests, scenarios = self._tree(
            tmp_path,
            "def test_a():\n"
            '    run("my_drill"); run("dusty_drill")\n'
            '    fire("covered.point")\n',
        )
        # point the scenario file at the uncovered point: the scenario
        # is exercised, so the point counts as covered
        open(scenarios, "a").write('PLAN = "uncovered.point:error"\n')
        got = list(
            injection_coverage.check_coverage(
                faults, tests, scenarios_path=scenarios
            )
        )
        assert not got, [v.render() for v in got]


class TestBaseline:
    def _fixture_violations(self):
        return run_lint(
            [_FIXTURES], passes=list(ALL_PASSES), repo_root=_REPO
        )

    def test_round_trip(self, tmp_path):
        first = self._fixture_violations()
        assert first.violations  # the planted set
        path = str(tmp_path / "baseline.json")
        Baseline.from_violations(
            first.violations, reason="fixture grandfather"
        ).save(path)
        again = run_lint(
            [_FIXTURES],
            passes=list(ALL_PASSES),
            baseline=Baseline.load(path),
            repo_root=_REPO,
        )
        assert not again.violations
        assert again.baselined == len(first.violations)
        assert not again.stale_baseline
        # the bare-ignore error is NOT baselineable
        assert again.errors

    def test_stale_entry_reported(self, tmp_path):
        first = self._fixture_violations()
        bl = Baseline.from_violations(first.violations, reason="ok")
        bl.entries.append(
            type(bl.entries[0])(
                pass_id="host-sync",
                path="tests/lint_fixtures/fx_host_sync.py",
                code="this_line_was_fixed_long_ago()",
                reason="ghost of a fixed site",
            )
        )
        path = str(tmp_path / "baseline.json")
        bl.save(path)
        again = run_lint(
            [_FIXTURES],
            passes=list(ALL_PASSES),
            baseline=Baseline.load(path),
            repo_root=_REPO,
        )
        assert len(again.stale_baseline) == 1
        assert again.stale_baseline[0].code == "this_line_was_fixed_long_ago()"
        assert not again.clean

    def test_entry_without_reason_is_an_error(self, tmp_path):
        first = self._fixture_violations()
        bl = Baseline.from_violations(first.violations, reason="")
        path = str(tmp_path / "baseline.json")
        bl.save(path)
        again = run_lint(
            [_FIXTURES],
            passes=list(ALL_PASSES),
            baseline=Baseline.load(path),
            repo_root=_REPO,
        )
        assert any("no reason" in e for e in again.errors)


class TestCli:
    def test_list_passes(self, capsys):
        assert lint_main(["--list-passes"]) == 0
        out = capsys.readouterr().out
        for pid in PASS_BY_ID:
            assert pid in out

    def test_unknown_pass_is_usage_error(self, capsys):
        assert lint_main(["--select", "no-such-pass", _FIXTURES]) == 2

    def test_fixtures_fail_and_json_format(self, capsys):
        rc = lint_main(
            ["--no-baseline", "--format", "json", _FIXTURES]
        )
        assert rc == 1
        data = json.loads(capsys.readouterr().out)
        assert data["findings"] and not data["clean"]

    def test_json_schema_round_trips(self, capsys):
        """The --format json report is the machine contract the lint
        gate diffs across commits: schema-stamped, deterministically
        sorted, and exactly reconstructable from a direct run_lint —
        including suppressed findings and their reasons."""
        from dlrover_tpu.analysis.cli import JSON_SCHEMA, findings_json

        rc = lint_main(["--no-baseline", "--format", "json", _FIXTURES])
        assert rc == 1
        data = json.loads(capsys.readouterr().out)
        assert data["schema"] == JSON_SCHEMA

        direct = run_lint(
            [_FIXTURES], passes=list(ALL_PASSES), repo_root=_REPO
        )
        expect = findings_json(direct)
        # byte-for-byte identical after a JSON round trip: the report
        # is diffable across commits with no run-order noise
        assert json.loads(json.dumps(expect)) == data

        # every finding carries the full key tuple; rules are the
        # line-number-free identities the baseline also matches on
        for f in data["findings"]:
            assert set(f) == {
                "pass", "file", "line", "rule", "message",
                "suppressed", "reason",
            }
            if f["suppressed"]:
                assert f["reason"].strip() or f["file"].endswith(
                    "fx_bad_suppression.py"
                )
        keys = [
            (f["file"], f["line"], f["pass"], f["rule"], f["suppressed"])
            for f in data["findings"]
        ]
        assert keys == sorted(keys)
        assert data["counts"]["violations"] == len(direct.violations)
        assert data["counts"]["suppressed"] == len(direct.suppressed)
        # the bare-ignore fixture keeps the errors channel non-empty
        assert data["counts"]["errors"] == len(direct.errors) > 0

    def test_write_baseline_then_clean(self, tmp_path, capsys):
        path = str(tmp_path / "bl.json")
        assert lint_main([_FIXTURES, "--write-baseline", path]) == 0
        capsys.readouterr()
        # violations are baselined now, but the bare ignore still fails
        rc = lint_main([_FIXTURES, "--baseline", path])
        out = capsys.readouterr().out
        assert "0 violations" in out
        assert rc == 1 and "needs a reason" in out


class TestSuppressionForms:
    def test_stacked_comment_lines_chain_up(self, tmp_path):
        p = tmp_path / "fx.py"
        p.write_text(
            "import time, threading\n"
            "_lock = threading.Lock()\n"
            "def f():\n"
            "    with _lock:\n"
            "        # tpulint: ignore[blocking-under-lock] long reason\n"
            "        # that wraps onto a second comment line\n"
            "        time.sleep(1)\n"
        )
        r = _run(str(p), blocking_under_lock)
        assert not r.violations and len(r.suppressed) == 1

    def test_suppression_for_other_pass_does_not_apply(self, tmp_path):
        p = tmp_path / "fx.py"
        p.write_text(
            "import time, threading\n"
            "_lock = threading.Lock()\n"
            "def f():\n"
            "    with _lock:\n"
            "        time.sleep(1)  # tpulint: ignore[host-sync] wrong pass\n"
        )
        r = _run(str(p), blocking_under_lock)
        assert len(r.violations) == 1 and not r.suppressed


class TestLockOrderMachinery:
    def test_closure_edges_participate(self, tmp_path):
        """The PR 8 drain threads are nested defs: a cycle whose second
        arm lives in a closure must still be found."""
        p = tmp_path / "fx.py"
        p.write_text(
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._a_lock = threading.Lock()\n"
            "        self._b_lock = threading.Lock()\n"
            "    def fwd(self):\n"
            "        with self._a_lock:\n"
            "            with self._b_lock:\n"
            "                pass\n"
            "    def spawn(self):\n"
            "        def drain():\n"
            "            with self._b_lock:\n"
            "                with self._a_lock:\n"
            "                    pass\n"
            "        threading.Thread(target=drain, daemon=True).start()\n"
        )
        r = _run(str(p), lock_order)
        assert len(r.violations) == 1, [v.render() for v in r.violations]
        assert "cycle" in r.violations[0].message

    def test_consistent_order_is_clean(self, tmp_path):
        p = tmp_path / "fx.py"
        p.write_text(
            "import threading\n"
            "_a_lock = threading.Lock()\n"
            "_b_lock = threading.Lock()\n"
            "def f():\n"
            "    with _a_lock:\n"
            "        with _b_lock:\n"
            "            pass\n"
            "def g():\n"
            "    with _a_lock:\n"
            "        with _b_lock:\n"
            "            pass\n"
        )
        r = _run(str(p), lock_order)
        assert not r.violations

    def test_reentrant_same_lock_is_not_a_cycle(self, tmp_path):
        p = tmp_path / "fx.py"
        p.write_text(
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._rlock = threading.RLock()\n"
            "    def outer(self):\n"
            "        with self._rlock:\n"
            "            self.inner()\n"
            "    def inner(self):\n"
            "        with self._rlock:\n"
            "            pass\n"
        )
        r = _run(str(p), lock_order)
        assert not r.violations

    def test_transitive_call_chain_closes_cycle(self, tmp_path):
        """a held -> call f -> call g -> acquires b; elsewhere b->a."""
        p = tmp_path / "fx.py"
        p.write_text(
            "import threading\n"
            "_a_lock = threading.Lock()\n"
            "_b_lock = threading.Lock()\n"
            "def top():\n"
            "    with _a_lock:\n"
            "        mid()\n"
            "def mid():\n"
            "    leaf()\n"
            "def leaf():\n"
            "    with _b_lock:\n"
            "        pass\n"
            "def reverse():\n"
            "    with _b_lock:\n"
            "        with _a_lock:\n"
            "            pass\n"
        )
        r = _run(str(p), lock_order)
        assert len(r.violations) == 1, [v.render() for v in r.violations]


class TestThreadLifecycleMachinery:
    def test_handle_passed_to_reaper_counts(self, tmp_path):
        p = tmp_path / "fx.py"
        p.write_text(
            "import subprocess\n"
            "class C:\n"
            "    def launch(self):\n"
            "        self._proc = subprocess.Popen(['true'])\n"
            "    def stop(self):\n"
            "        kill_process_group(self._proc, grace_s=5)\n"
        )
        r = _run(str(p), thread_lifecycle)
        assert not r.violations

    def test_killpg_on_pid_is_not_a_reap(self, tmp_path):
        """The warm-spare bug shape: os.killpg(getpgid(pid)) never
        waits — the handle itself is unreaped."""
        p = tmp_path / "fx.py"
        p.write_text(
            "import os, signal, subprocess\n"
            "class C:\n"
            "    def launch(self):\n"
            "        self._proc = subprocess.Popen(['true'])\n"
            "    def stop(self):\n"
            "        os.killpg(os.getpgid(self._proc.pid), signal.SIGKILL)\n"
        )
        r = _run(str(p), thread_lifecycle)
        assert len(r.violations) == 1
        assert "_proc" in r.violations[0].message

    def test_loop_over_container_join_counts(self, tmp_path):
        p = tmp_path / "fx.py"
        p.write_text(
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._threads = []\n"
            "    def go(self):\n"
            "        self._threads.append(threading.Thread(target=int))\n"
            "    def stop(self):\n"
            "        for t in self._threads:\n"
            "            t.join(timeout=5)\n"
        )
        r = _run(str(p), thread_lifecycle)
        assert not r.violations

    def test_untimed_join_does_not_satisfy(self, tmp_path):
        p = tmp_path / "fx.py"
        p.write_text(
            "import threading\n"
            "class C:\n"
            "    def go(self):\n"
            "        self._t = threading.Thread(target=int)\n"
            "    def stop(self):\n"
            "        self._t.join()\n"
        )
        r = _run(str(p), thread_lifecycle)
        assert len(r.violations) == 1


class TestExceptionSwallowMachinery:
    def test_broad_in_tuple_is_flagged(self, tmp_path):
        p = tmp_path / "fx.py"
        p.write_text(
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except (ValueError, Exception):\n"
            "        pass\n"
        )
        r = _run(str(p), exception_swallow)
        assert len(r.violations) == 1

    def test_handler_in_nested_def_does_not_count(self, tmp_path):
        """A log call inside a nested def runs later, if ever — the
        handler still swallows."""
        p = tmp_path / "fx.py"
        p.write_text(
            "import logging\n"
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:\n"
            "        def later():\n"
            "            logging.warning('x')\n"
            "        keep = later\n"
        )
        r = _run(str(p), exception_swallow)
        assert len(r.violations) == 1

    def test_counter_bump_counts(self, tmp_path):
        p = tmp_path / "fx.py"
        p.write_text(
            "def f(stats):\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:\n"
            "        stats['fail'] += 1\n"
        )
        r = _run(str(p), exception_swallow)
        assert not r.violations


class TestEndpointConformanceMachinery:
    def _ctx(self, tmp_path, name, source):
        from dlrover_tpu.analysis.core import FileContext

        p = tmp_path / name
        p.write_text(source)
        return FileContext.parse(str(p), name)

    def test_route_referenced_only_by_docs_is_clean(self, tmp_path):
        server = self._ctx(
            tmp_path,
            "server.py",
            "class H:\n"
            "    def do_GET(self):\n"
            "        if self.path == '/fx/status':\n"
            "            pass\n",
        )
        got = list(
            endpoint_conformance.check_conformance(
                [server], "curl the `/fx/status` endpoint"
            )
        )
        assert not got
        got = list(endpoint_conformance.check_conformance([server], ""))
        assert len(got) == 1 and got[0].code == "route:/fx/status"

    def test_helper_call_path_not_first_arg(self, tmp_path):
        """The gateway shape: _post_replica(h, '/v1/x', payload)."""
        client = self._ctx(
            tmp_path,
            "client.py",
            "class C:\n"
            "    def go(self, h):\n"
            "        self._post_replica(h, '/fx/x', {})\n",
        )
        got = list(endpoint_conformance.check_conformance([client], ""))
        assert len(got) == 1 and got[0].code == "client:/fx/x"

    def test_fstring_url_tail_collected(self, tmp_path):
        client = self._ctx(
            tmp_path,
            "client.py",
            "def go(host, port):\n"
            "    url = f'http://{host}:{port}/fx/poll'\n"
            "    return url\n",
        )
        got = list(endpoint_conformance.check_conformance([client], ""))
        assert len(got) == 1 and got[0].code == "client:/fx/poll"

    def test_filesystem_paths_are_not_clients(self, tmp_path):
        client = self._ctx(
            tmp_path,
            "client.py",
            "import os\n"
            "def go(base_dir):\n"
            "    return os.path.join(base_dir, '/tmp/x.json')\n",
        )
        got = list(endpoint_conformance.check_conformance([client], ""))
        assert not got


class TestChangedMode:
    def _git_repo(self, tmp_path):
        import subprocess

        def git(*args):
            subprocess.run(
                ["git", "-c", "user.email=t@t", "-c", "user.name=t"]
                + list(args),
                cwd=tmp_path,
                check=True,
                capture_output=True,
            )

        (tmp_path / "pyproject.toml").write_text("[project]\n")
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        violation = (
            "import threading, time\n"
            "_lock = threading.Lock()\n"
            "def f():\n"
            "    with _lock:\n"
            "        time.sleep(1)\n"
        )
        (pkg / "old.py").write_text(violation)
        (pkg / "other.py").write_text("X = 1\n")
        git("init", "-q")
        git("add", "-A")
        git("commit", "-qm", "seed")
        return pkg, violation

    def test_changed_lints_only_changed_files(self, tmp_path, capsys):
        pkg, violation = self._git_repo(tmp_path)
        # old.py's committed violation must NOT be reported; the fresh
        # edit to other.py must be
        (pkg / "other.py").write_text(violation)
        rc = lint_main(["--changed", "--no-baseline", str(pkg)])
        captured = capsys.readouterr()
        assert rc == 1
        assert "other.py" in captured.out and "old.py" not in captured.out
        # the notice rides stderr: stdout belongs to --format json
        assert "skips repo-wide passes" in captured.err

    def test_changed_with_no_edits_is_clean(self, tmp_path, capsys):
        pkg, _ = self._git_repo(tmp_path)
        rc = lint_main(["--changed", "--no-baseline", str(pkg)])
        captured = capsys.readouterr()
        assert rc == 0
        assert "no Python files changed" in captured.err

    def test_changed_json_stdout_is_pure(self, tmp_path, capsys):
        """Review regression: the --changed notices must not corrupt the
        --format json machine contract — stdout parses as the schema
        document, notices go to stderr."""
        pkg, violation = self._git_repo(tmp_path)
        (pkg / "other.py").write_text(violation)
        rc = lint_main(
            ["--changed", "--no-baseline", "--format", "json", str(pkg)]
        )
        captured = capsys.readouterr()
        doc = json.loads(captured.out)
        assert rc == 1
        assert doc["schema"] == "tpurun-lint-findings/1"
        assert doc["counts"]["violations"] == 1
        assert "skips repo-wide passes" in captured.err

    def test_changed_json_no_edits_emits_empty_document(
        self, tmp_path, capsys
    ):
        """A gate diffing findings across commits always gets a
        document, even when nothing changed."""
        pkg, _ = self._git_repo(tmp_path)
        rc = lint_main(
            ["--changed", "--no-baseline", "--format", "json", str(pkg)]
        )
        captured = capsys.readouterr()
        doc = json.loads(captured.out)
        assert rc == 0
        assert doc["clean"] is True and doc["findings"] == []
        assert "no Python files changed" in captured.err

    def test_changed_sees_untracked_files(self, tmp_path, capsys):
        pkg, violation = self._git_repo(tmp_path)
        (pkg / "fresh.py").write_text(violation)
        rc = lint_main(["--changed", "--no-baseline", str(pkg)])
        out = capsys.readouterr().out
        assert rc == 1 and "fresh.py" in out

    def test_changed_rejects_write_baseline(self, tmp_path, capsys):
        """A subset run must not silently truncate the repo-wide
        baseline file."""
        pkg, _ = self._git_repo(tmp_path)
        rc = lint_main(
            [
                "--changed",
                "--write-baseline",
                str(tmp_path / "bl.json"),
                str(pkg),
            ]
        )
        assert rc == 2
        assert "cannot be combined" in capsys.readouterr().err

    def test_changed_with_only_repo_passes_is_usage_error(
        self, tmp_path, capsys
    ):
        """--select naming only repo-wide passes + --changed must not
        exit 0 having checked nothing."""
        pkg, violation = self._git_repo(tmp_path)
        (pkg / "other.py").write_text(violation)
        rc = lint_main(
            ["--changed", "--select", "endpoint-conformance", str(pkg)]
        )
        assert rc == 2
        assert "no runnable pass" in capsys.readouterr().err


class TestReviewRegressions:
    """Review findings on PR 6 itself: the staleness rule must not be
    satisfied by the registry's own declaration, bare ignores on
    repo-level violations are errors too, and the CLI refuses to
    green-light a typo'd path."""

    def _fake_tree(self, tmp_path, mod_source):
        (tmp_path / "pyproject.toml").write_text("[project]\n")
        common = tmp_path / "dlrover_tpu" / "common"
        common.mkdir(parents=True)
        (common / "constants.py").write_text(
            "class K:\n"
            "    def __init__(self, name, internal=False,"
            " context_field=''):\n"
            "        self.name = name\n"
            "        self.internal = internal\n"
            "        self.context_field = context_field\n"
            "\n"
            "class NodeEnv:\n"
            "    ATTR_ONLY = 'DLROVER_ATTR_ONLY'\n"
            "\n"
            "ENV_KNOBS = {k.name: k for k in [\n"
            "    K('DLROVER_USED', internal=True),\n"
            "    K('DLROVER_GHOST', internal=True),\n"
            "    K('DLROVER_ATTR_ONLY', internal=True),\n"
            "]}\n"
        )
        (tmp_path / "dlrover_tpu" / "mod.py").write_text(mod_source)
        return tmp_path

    def test_registry_self_reference_does_not_hide_staleness(
        self, tmp_path
    ):
        root = self._fake_tree(
            tmp_path,
            "import os\n"
            "A = os.getenv('DLROVER_USED')\n"
            "from .common.constants import NodeEnv\n"
            "B = os.getenv(NodeEnv.ATTR_ONLY)\n",
        )
        r = run_lint(
            [str(root / "dlrover_tpu")],
            passes=[env_knobs],
            repo_root=str(root),
        )
        codes = {v.code for v in r.violations}
        # GHOST appears ONLY in ENV_KNOBS itself -> stale; USED is
        # referenced by literal, ATTR_ONLY through the NodeEnv attr
        assert "stale:DLROVER_GHOST" in codes, [
            v.render() for v in r.violations
        ]
        assert "stale:DLROVER_USED" not in codes
        assert "stale:DLROVER_ATTR_ONLY" not in codes

    def test_bare_ignore_on_repo_level_violation_is_an_error(
        self, tmp_path
    ):
        root = self._fake_tree(
            tmp_path,
            "X = 'DLROVER_TYPO_KNOB'  # tpulint: ignore[env-knobs]\n"
            "import os\n"
            "A = os.getenv('DLROVER_USED')\n"
            "B = 'DLROVER_ATTR_ONLY'\n",
        )
        r = run_lint(
            [str(root / "dlrover_tpu")],
            passes=[env_knobs],
            repo_root=str(root),
        )
        assert any(
            v.pass_id == "env-knobs" for v, _s in r.suppressed
        ), [v.render() for v in r.violations]
        assert any("needs a reason" in e for e in r.errors)
        assert not r.clean

    def test_cli_rejects_nonexistent_path(self, capsys):
        assert lint_main(["definitely_no_such_dir_xyz"]) == 2
        assert "do not exist" in capsys.readouterr().err

    def test_cli_rejects_pathless_lint(self, tmp_path, capsys):
        empty = tmp_path / "empty"
        empty.mkdir()
        assert lint_main([str(empty)]) == 2
        assert "no Python files" in capsys.readouterr().err


class TestMeshAxesMachinery:
    """Fake-tree cases: the registry cross-checks must catch drift in
    every direction, not just unknown literals."""

    def _tree(self, tmp_path, mesh_src, sharding_src="", probe_src=""):
        tmp_path.mkdir(parents=True, exist_ok=True)
        (tmp_path / "pyproject.toml").write_text("[project]\n")
        par = tmp_path / "dlrover_tpu" / "parallel"
        par.mkdir(parents=True)
        (par / "mesh.py").write_text(mesh_src)
        if sharding_src:
            (par / "sharding.py").write_text(sharding_src)
        if probe_src:
            (tmp_path / "dlrover_tpu" / "probe.py").write_text(probe_src)
        return tmp_path

    _REGISTRY = (
        "MESH_AXIS_REGISTRY = {\n"
        '    "dp": ("mesh", "data"),\n'
        '    "tp": ("mesh", "tensor"),\n'
        '    "batch": ("logical", "batch"),\n'
        "}\n"
        'MESH_AXES = ("dp", "tp")\n'
    )
    _RULES = 'DEFAULT_RULES = [("batch", ("dp",))]\n'

    def _lint(self, root):
        return run_lint(
            [str(root / "dlrover_tpu")],
            passes=[mesh_axes],
            repo_root=str(root),
        )

    def test_conformant_fake_tree_is_clean(self, tmp_path):
        root = self._tree(
            tmp_path,
            self._REGISTRY,
            self._RULES,
            "from jax.sharding import PartitionSpec\n"
            "def f(mesh):\n"
            '    return PartitionSpec("batch"), mesh.shape["dp"]\n',
        )
        r = self._lint(root)
        assert not r.violations, [v.render() for v in r.violations]

    @pytest.mark.parametrize("call", [
        'param_with_axes("w", init, (4,), axes=("dp",))',
        'constrain(init, "dp")',  # models/layers.py's name for the constraint
    ])
    def test_mesh_axis_in_logical_annotation_flagged(self, tmp_path, call):
        """A mesh axis in param_with_axes, or in a constraint, is the
        silent-no-constraint drift even though the name is registered."""
        root = self._tree(
            tmp_path,
            self._REGISTRY,
            self._RULES,
            f"def f(init):\n    return {call}\n",
        )
        r = self._lint(root)
        assert len(r.violations) == 1
        assert "requires a logical axis" in r.violations[0].message

    def test_logical_axis_in_collective_flagged(self, tmp_path):
        root = self._tree(
            tmp_path,
            self._REGISTRY,
            self._RULES,
            "import jax\n"
            "def f(x):\n"
            '    return jax.lax.psum(x, "batch")\n',
        )
        r = self._lint(root)
        assert len(r.violations) == 1
        assert "requires a mesh axis" in r.violations[0].message

    def test_mesh_axes_tuple_drift_flagged(self, tmp_path):
        registry = self._REGISTRY.replace(
            'MESH_AXES = ("dp", "tp")', 'MESH_AXES = ("dp",)'
        )
        root = self._tree(tmp_path, registry, self._RULES)
        r = self._lint(root)
        codes = {v.code for v in r.violations}
        assert "mesh-axes-drift" in codes, [
            v.render() for v in r.violations
        ]

    def test_mesh_construction_with_unregistered_axes_flagged(
        self, tmp_path
    ):
        registry = self._REGISTRY + (
            "def build(devs):\n"
            '    return Mesh(devs, ("dp", "zz_rogue"))\n'
        )
        root = self._tree(tmp_path, registry, self._RULES)
        r = self._lint(root)
        assert any(
            "Mesh(...)" in v.message and "zz_rogue" in v.message
            for v in r.violations
        ), [v.render() for v in r.violations]

    def test_suppressed_site_outside_lint_subset_honored(self, tmp_path):
        """Review regression: the hybrid repo_check scans the whole
        tree even when run_lint's subset (--changed) excludes the
        suppressed file — its inline suppression must still be
        honored, or the pre-commit fast path blocks commits the full
        gate accepts."""
        registry = self._REGISTRY + (
            "def build(devs):\n"
            '    return Mesh(devs, ("dp", "zz_probe"))'
            "  # tpulint: ignore[mesh-axes] drill mesh, not a training axis\n"
        )
        root = self._tree(tmp_path, registry, self._RULES, "X = 1\n")
        r = run_lint(
            [str(root / "dlrover_tpu" / "probe.py")],
            passes=[mesh_axes],
            repo_root=str(root),
        )
        assert not r.violations, [v.render() for v in r.violations]
        assert any(v.pass_id == "mesh-axes" for v, _s in r.suppressed)

    def test_mesh_construction_keyword_form_checked(self, tmp_path):
        """Review regression: jax's Mesh accepts axis_names as a
        keyword — the cross-check must not skip that form."""
        registry = self._REGISTRY + (
            "def build(devs):\n"
            '    return Mesh(devs, axis_names=("dp", "zz_kwrogue"))\n'
        )
        root = self._tree(tmp_path, registry, self._RULES)
        r = self._lint(root)
        assert any(
            "zz_kwrogue" in v.message for v in r.violations
        ), [v.render() for v in r.violations]

    def test_default_rules_unregistered_target_flagged(self, tmp_path):
        root = self._tree(
            tmp_path,
            self._REGISTRY,
            'DEFAULT_RULES = [("batch", ("zz_ghost_mesh",))]\n',
        )
        r = self._lint(root)
        codes = {v.code for v in r.violations}
        assert "rule-target:batch:zz_ghost_mesh" in codes

    def test_unmapped_logical_axis_flagged(self, tmp_path):
        registry = self._REGISTRY.replace(
            '    "batch": ("logical", "batch"),\n',
            '    "batch": ("logical", "batch"),\n'
            '    "seq": ("logical", "sequence"),\n',
        )
        # seq registered + referenced by a spec, but DEFAULT_RULES
        # never maps it
        root = self._tree(
            tmp_path,
            registry,
            self._RULES,
            "from jax.sharding import PartitionSpec as P\n"
            'S = P("seq")\n',
        )
        r = self._lint(root)
        codes = {v.code for v in r.violations}
        assert "unmapped:seq" in codes, [v.render() for v in r.violations]

    def test_stale_registry_entry_flagged(self, tmp_path):
        registry = self._REGISTRY.replace(
            '    "batch": ("logical", "batch"),\n',
            '    "batch": ("logical", "batch"),\n'
            '    "zz_unused": ("logical", "nobody references this"),\n',
        )
        rules = (
            'DEFAULT_RULES = [("batch", ("dp",)), ("zz_unused", None)]\n'
        )
        root = self._tree(tmp_path, registry, rules)
        r = self._lint(root)
        # mapped by DEFAULT_RULES -> referenced -> NOT stale
        assert not any("stale" in v.code for v in r.violations)
        root2 = self._tree(
            tmp_path / "two", registry, self._RULES
        )
        r2 = self._lint(root2)
        codes = {v.code for v in r2.violations}
        assert "stale:zz_unused" in codes
        # registered-but-unmapped also fires for it
        assert "unmapped:zz_unused" in codes

    def test_computed_registry_is_a_parse_violation(self, tmp_path):
        root = self._tree(
            tmp_path,
            "MESH_AXIS_REGISTRY = dict(dp=(\"mesh\", \"d\"))\n"
            "MESH_AXES = tuple(MESH_AXIS_REGISTRY)\n",
        )
        r = self._lint(root)
        assert any(v.code == "registry-parse" for v in r.violations)

    def test_registry_edit_reparsed_within_one_process(self, tmp_path):
        """Review regression: the pass singleton caches the parsed
        registry keyed by (root, mtime/size) — registering the axis and
        re-running run_lint in the SAME process must go clean (watch
        modes, harnesses looping over one tmp root)."""
        probe = (
            "from jax.sharding import PartitionSpec\n"
            'SPEC = PartitionSpec("zz_new")\n'
        )
        root = self._tree(tmp_path, self._REGISTRY, self._RULES, probe)
        r = self._lint(root)
        assert any("zz_new" in v.message for v in r.violations)
        (root / "dlrover_tpu" / "parallel" / "mesh.py").write_text(
            self._REGISTRY.replace(
                '    "batch": ("logical", "batch"),\n',
                '    "batch": ("logical", "batch"),\n'
                '    "zz_new": ("logical", "fresh"),\n',
            )
        )
        (root / "dlrover_tpu" / "parallel" / "sharding.py").write_text(
            'DEFAULT_RULES = [("batch", ("dp",)), ("zz_new", ("tp",))]\n'
        )
        r2 = self._lint(root)
        assert not r2.violations, [v.render() for v in r2.violations]


class TestReshardCoverageMachinery:
    """Fake-tree cases over the rule-table cross-checks."""

    _MESH = (
        "MESH_AXIS_REGISTRY = {\n"
        '    "dp": ("mesh", "d"),\n'
        '    "tp": ("mesh", "t"),\n'
        '    "batch": ("logical", "b"),\n'
        "}\n"
        'MESH_AXES = ("dp", "tp")\n'
    )

    def _tree(self, tmp_path, sharding_src, train_state_fields=("step",)):
        (tmp_path / "pyproject.toml").write_text("[project]\n")
        par = tmp_path / "dlrover_tpu" / "parallel"
        par.mkdir(parents=True)
        (par / "mesh.py").write_text(self._MESH)
        (par / "sharding.py").write_text(sharding_src)
        fields = "".join(f"    {f}: int\n" for f in train_state_fields)
        (par / "train_step.py").write_text(
            "class TrainState:\n" + fields
        )
        return tmp_path

    def _lint(self, root):
        return run_lint(
            [str(root / "dlrover_tpu")],
            passes=[reshard_coverage],
            repo_root=str(root),
        )

    _BASE = (
        'DEFAULT_RULES = [("batch", ("dp",))]\n'
        'ELASTIC_AXES = ("dp",)\n'
        'RESHARD_POLICIES = ("replicate", "respec")\n'
    )

    def test_conformant_table_is_clean(self, tmp_path):
        root = self._tree(
            tmp_path,
            self._BASE
            + 'RESHARD_RULES = {"step": ("replicate", ()),'
            ' "params": ("respec", ("dp", "tp"))}\n',
            train_state_fields=("step", "params"),
        )
        r = self._lint(root)
        assert not r.violations, [v.render() for v in r.violations]

    def test_train_state_field_without_rule_flagged(self, tmp_path):
        root = self._tree(
            tmp_path,
            self._BASE + 'RESHARD_RULES = {"step": ("replicate", ())}\n',
            train_state_fields=("step", "ema_params"),
        )
        r = self._lint(root)
        codes = {v.code for v in r.violations}
        assert "uncovered:ema_params" in codes, [
            v.render() for v in r.violations
        ]

    def test_stale_rule_flagged(self, tmp_path):
        root = self._tree(
            tmp_path,
            self._BASE
            + 'RESHARD_RULES = {"step": ("replicate", ()),'
            ' "zz_gone": ("replicate", ())}\n',
        )
        r = self._lint(root)
        assert any("stale:zz_gone" == v.code for v in r.violations)

    def test_unknown_policy_flagged(self, tmp_path):
        root = self._tree(
            tmp_path,
            self._BASE + 'RESHARD_RULES = {"step": ("teleport", ())}\n',
        )
        r = self._lint(root)
        assert any("policy:step" == v.code for v in r.violations)

    def test_axis_gap_vs_default_rules_flagged(self, tmp_path):
        """DEFAULT_RULES can shard over tp, but the respec rule only
        covers dp — the save path can produce a sharding the table
        never answers for."""
        root = self._tree(
            tmp_path,
            'DEFAULT_RULES = [("batch", ("dp", "tp"))]\n'
            'ELASTIC_AXES = ("dp",)\n'
            'RESHARD_POLICIES = ("replicate", "respec")\n'
            'RESHARD_RULES = {"step": ("replicate", ()),'
            ' "params": ("respec", ("dp",))}\n',
            train_state_fields=("step", "params"),
        )
        r = self._lint(root)
        assert any(
            v.code == "axis-gap:params:tp" for v in r.violations
        ), [v.render() for v in r.violations]

    def test_rung_gap_vs_elastic_axes_flagged(self, tmp_path):
        root = self._tree(
            tmp_path,
            'DEFAULT_RULES = [("batch", ("dp",))]\n'
            'ELASTIC_AXES = ("dp", "tp")\n'
            'RESHARD_POLICIES = ("replicate", "respec")\n'
            'RESHARD_RULES = {"step": ("replicate", ()),'
            ' "params": ("respec", ("dp",))}\n',
            train_state_fields=("step", "params"),
        )
        r = self._lint(root)
        assert any(
            v.code == "rung-gap:params:tp" for v in r.violations
        ), [v.render() for v in r.violations]

    def test_rung_gap_on_pp_axis_flagged(self, tmp_path):
        """The 2D rung ladder's axes (docs/elastic_parallelism.md):
        ELASTIC_AXES carries pp, so a respec rule that only answers for
        (dp, tp) cannot survive a dp→pp trade — the planner would pick
        a rung the reshard table never covers."""
        root = self._tree(
            tmp_path,
            'DEFAULT_RULES = [("batch", ("dp",))]\n'
            'ELASTIC_AXES = ("dp", "tp", "pp")\n'
            'RESHARD_POLICIES = ("replicate", "respec")\n'
            'RESHARD_RULES = {"step": ("replicate", ()),'
            ' "params": ("respec", ("dp", "tp"))}\n',
            train_state_fields=("step", "params"),
        )
        r = self._lint(root)
        codes = {v.code for v in r.violations}
        assert "rung-gap:params:pp" in codes, [
            v.render() for v in r.violations
        ]
        assert "rung-gap:params:tp" not in codes  # tp IS covered

    def test_missing_table_flagged(self, tmp_path):
        root = self._tree(tmp_path, "DEFAULT_RULES = []\n")
        r = self._lint(root)
        assert any(v.code == "table-parse" for v in r.violations)

    def test_unreadable_train_state_is_parse_finding_not_stale(
        self, tmp_path
    ):
        """Review regression: a mid-edit syntax error in train_step.py
        must NOT misreport every rule as 'stale entry; delete it' —
        one parse finding, coverage checks skipped."""
        root = self._tree(
            tmp_path,
            self._BASE + 'RESHARD_RULES = {"step": ("replicate", ())}\n',
        )
        (root / "dlrover_tpu" / "parallel" / "train_step.py").write_text(
            "def broken(:\n"
        )
        r = self._lint(root)
        codes = {v.code for v in r.violations}
        assert "trainstate-parse" in codes, [
            v.render() for v in r.violations
        ]
        assert not any(c.startswith("stale:") for c in codes)

    def test_rule_table_edit_reparsed_within_one_process(self, tmp_path):
        """Review regression: same (root, mtime/size)-keyed cache as
        mesh-axes — adding the missing rule and re-running run_lint in
        the SAME process must go clean."""
        root = self._tree(
            tmp_path, self._BASE + "RESHARD_RULES = {}\n"
        )
        r = self._lint(root)
        assert any(v.code == "uncovered:step" for v in r.violations)
        (root / "dlrover_tpu" / "parallel" / "sharding.py").write_text(
            self._BASE + 'RESHARD_RULES = {"step": ("replicate", ())}\n'
        )
        r2 = self._lint(root)
        assert not r2.violations, [v.render() for v in r2.violations]

    def test_extra_kwarg_without_rule_flagged(self, tmp_path):
        root = self._tree(
            tmp_path,
            self._BASE + 'RESHARD_RULES = {"step": ("replicate", ())}\n',
        )
        (tmp_path / "dlrover_tpu" / "probe.py").write_text(
            "def f(engine, step, tree, cursors):\n"
            "    return engine.save_to_memory(step, tree, extra=cursors)\n"
        )
        r = self._lint(root)
        assert any(
            "extra" in v.message and v.path.endswith("probe.py")
            for v in r.violations
        ), [v.render() for v in r.violations]

    def test_real_repo_tables_are_loadable_and_match_runtime(self):
        """The AST-parsed tables must agree with what the runtime
        imports — a computed entry would silently vanish from lint."""
        jax = pytest.importorskip("jax")  # noqa: F841 — sharding imports jax
        from dlrover_tpu.analysis.passes.reshard_coverage import (
            load_tables,
            train_state_fields,
        )
        from dlrover_tpu.parallel import sharding as runtime

        rules, policies, elastic = load_tables(_REPO)
        assert rules == runtime.RESHARD_RULES
        assert policies == runtime.RESHARD_POLICIES
        assert elastic == runtime.ELASTIC_AXES
        assert set(train_state_fields(_REPO)) == {
            "step", "params", "opt_state",
        }

    def test_real_repo_registry_matches_runtime(self):
        jax = pytest.importorskip("jax")  # noqa: F841 — mesh imports jax
        from dlrover_tpu.analysis.passes.mesh_axes import load_axis_registry
        from dlrover_tpu.parallel import mesh as runtime

        registry, axes, err = load_axis_registry(
            os.path.join(_REPO, "dlrover_tpu", "parallel", "mesh.py")
        )
        assert not err
        assert axes == runtime.MESH_AXES
        assert registry == {
            k: v[0] for k, v in runtime.MESH_AXIS_REGISTRY.items()
        }


class TestJournalConformanceMachinery:
    def _ctx(self, tmp_path, name, source):
        from dlrover_tpu.analysis.core import FileContext

        p = tmp_path / name
        p.write_text(source)
        return FileContext.parse(str(p), name)

    def test_capture_restore_key_mismatch_flagged(self, tmp_path):
        ctx = self._ctx(
            tmp_path,
            "persistence.py",
            "def capture_master_state(master):\n"
            '    return {"job": 1, "kv": 2}\n'
            "def restore_master_state(master, state):\n"
            '    use(state.get("job"))\n'
            '    use(state.get("phantom"))\n',
        )
        got = list(journal_conformance.repo_check(str(tmp_path), [ctx]))
        codes = {v.code for v in got}
        assert "capture-only:kv" in codes
        assert "restore-only:phantom" in codes

    def test_subscript_restore_read_counts(self, tmp_path):
        ctx = self._ctx(
            tmp_path,
            "persistence.py",
            "def capture_master_state(master):\n"
            '    return {"job": 1}\n'
            "def restore_master_state(master, state):\n"
            '    use(state["job"])\n',
        )
        got = list(journal_conformance.repo_check(str(tmp_path), [ctx]))
        assert not got, [v.render() for v in got]

    def test_direct_journal_call_is_a_recorder(self, tmp_path):
        """The rdzv manager journals via self.journal(...) directly —
        no _record wrapper."""
        ctx = self._ctx(
            tmp_path,
            "mgr.py",
            "class M:\n"
            "    def complete(self):\n"
            '        self.journal("fx.complete", {})\n',
        )
        got = list(journal_conformance.repo_check(str(tmp_path), [ctx]))
        # no applier in the tree -> recorder conformance is skipped
        # (a subset lint must not read every kind as unreplayable)
        assert not got
        applier = self._ctx(
            tmp_path,
            "persist.py",
            "def apply_wal_record(m, record):\n"
            '    kind = record.get("kind")\n'
            '    if kind == "fx.other":\n'
            "        pass\n",
        )
        got = list(
            journal_conformance.repo_check(str(tmp_path), [ctx, applier])
        )
        codes = {v.code for v in got}
        assert "recorded:fx.complete" in codes
        assert "applied:fx.other" in codes

    def test_non_dotted_literals_ignored(self, tmp_path):
        """Profiler timers call .record("train_step", ...) — not a WAL
        kind; the dotted-kind shape keeps them out of scope."""
        ctx = self._ctx(
            tmp_path,
            "timer.py",
            "class T:\n"
            "    def hit(self):\n"
            '        self.timer.record("train_step", 1, 2)\n'
            "def apply_wal_record(m, r):\n"
            '    kind = r.get("kind")\n'
            '    if kind == "fx.x":\n'
            "        pass\n",
        )
        got = list(journal_conformance.repo_check(str(tmp_path), [ctx]))
        assert not any("train_step" in v.code for v in got)

    def test_repo_kinds_conform_both_ways(self):
        """The real WAL protocol: every recorded kind has a branch and
        vice versa (the invariant the pass rails)."""
        from dlrover_tpu.analysis.core import FileContext, iter_py_files
        from dlrover_tpu.analysis.passes.journal_conformance import (
            collect_applied,
            collect_recorded,
        )

        rec, app = set(), set()
        for p in iter_py_files([os.path.join(_REPO, "dlrover_tpu")]):
            ctx = FileContext.parse(p, os.path.relpath(p, _REPO))
            if ctx is None:
                continue
            rec |= {k for k, _l in collect_recorded(ctx)}
            app |= {k for k, _l in collect_applied(ctx)}
        assert rec and rec == app, (rec - app, app - rec)


class TestEpochFenceMachinery:
    def _run_src(self, tmp_path, source):
        p = tmp_path / "fx.py"
        p.write_text(source)
        return _run(str(p), epoch_fence)

    def test_transport_built_outside_masterclient_flagged(self, tmp_path):
        r = self._run_src(
            tmp_path,
            "class SideChannel:\n"
            "    def __init__(self, addr):\n"
            "        self._t = HttpTransport(addr)\n",
        )
        assert len(r.violations) == 1
        assert "outside MasterClient" in r.violations[0].message

    def test_transport_built_inside_masterclient_clean(self, tmp_path):
        r = self._run_src(
            tmp_path,
            "class MasterClient:\n"
            "    def __init__(self, addr):\n"
            "        self._transport = HttpTransport(addr)\n",
        )
        assert not r.violations

    def test_kwargs_splat_does_not_count_as_stamp(self, tmp_path):
        r = self._run_src(
            tmp_path,
            "def respond(**kw):\n"
            "    return dumps(BaseResponse(**kw))\n",
        )
        assert len(r.violations) == 1
        assert "master_epoch" in r.violations[0].message

    def test_observe_epoch_in_nested_def_counts(self, tmp_path):
        """A retry closure that observes the epoch still fences the
        enclosing call path."""
        r = self._run_src(
            tmp_path,
            "class C:\n"
            "    def call(self, payload):\n"
            "        def once():\n"
            "            raw = self._transport.get(payload)\n"
            "            self._observe_epoch(raw)\n"
            "            return raw\n"
            "        return once()\n",
        )
        assert not r.violations

    def test_module_level_transport_call_flagged(self, tmp_path):
        r = self._run_src(
            tmp_path,
            "RAW = CLIENT._transport.report(b'x')\n",
        )
        assert len(r.violations) == 1

    def test_aliased_transport_method_flagged(self, tmp_path):
        """Review regression: the fence matches the ATTRIBUTE access,
        so the repo's own bound-method idiom
        (``fn = self._transport.get; fn(payload)``) cannot evade it in
        an unfenced function."""
        r = self._run_src(
            tmp_path,
            "class Rogue:\n"
            "    def fetch(self, verb, payload):\n"
            "        fn = (self._transport.get if verb == 'get'\n"
            "              else self._transport.report)\n"
            "        return fn(payload)\n",
        )
        assert len(r.violations) == 2, [
            v.render() for v in r.violations
        ]
        assert all("epoch fence" in v.message for v in r.violations)

    def test_aliased_transport_method_fenced_clean(self, tmp_path):
        """MasterClient._call's real shape: aliasing inside a function
        that observes the epoch is the fenced path."""
        r = self._run_src(
            tmp_path,
            "class C:\n"
            "    def _call(self, verb, payload):\n"
            "        fn = (self._transport.get if verb == 'get'\n"
            "              else self._transport.report)\n"
            "        raw = fn(payload)\n"
            "        self._observe_epoch(raw)\n"
            "        return raw\n",
        )
        assert not r.violations, [v.render() for v in r.violations]


class TestPrecommitHook:
    """The checked-in pre-commit fast path: scripts/precommit-lint on a
    throwaway git repo catches a planted violation in a CHANGED file
    and skips clean/committed files entirely."""

    _SCRIPT = os.path.join(_REPO, "scripts", "precommit-lint")

    def _git_repo(self, tmp_path):
        import subprocess

        def git(*args):
            subprocess.run(
                ["git", "-c", "user.email=t@t", "-c", "user.name=t"]
                + list(args),
                cwd=tmp_path,
                check=True,
                capture_output=True,
            )

        (tmp_path / "pyproject.toml").write_text("[project]\n")
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        violation = (
            "import threading, time\n"
            "_lock = threading.Lock()\n"
            "def f():\n"
            "    with _lock:\n"
            "        time.sleep(1)\n"
        )
        # a COMMITTED violation: the fast path must not report it
        (pkg / "old.py").write_text(violation)
        (pkg / "clean.py").write_text("X = 1\n")
        git("init", "-q")
        git("add", "-A")
        git("commit", "-qm", "seed")
        return pkg, violation

    def _hook(self, tmp_path, lint_path="pkg"):
        import subprocess
        import sys

        env = dict(os.environ)
        env["PRECOMMIT_ROOT"] = str(tmp_path)
        env["PYTHON"] = sys.executable
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
        return subprocess.run(
            ["sh", self._SCRIPT, lint_path],
            capture_output=True,
            text=True,
            timeout=120,
            env=env,
        )

    def test_catches_planted_violation_in_changed_file(self, tmp_path):
        pkg, violation = self._git_repo(tmp_path)
        (pkg / "fresh.py").write_text(violation)
        proc = self._hook(tmp_path)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "fresh.py" in proc.stdout
        assert "blocking-under-lock" in proc.stdout
        # the committed twin is skipped — the hook is a fast path, not
        # the repo gate
        assert "old.py" not in proc.stdout

    def test_skips_clean_tree(self, tmp_path):
        self._git_repo(tmp_path)
        proc = self._hook(tmp_path)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "no Python files changed" in proc.stderr

    def test_clean_edit_passes(self, tmp_path):
        pkg, _ = self._git_repo(tmp_path)
        (pkg / "clean.py").write_text("X = 2\n")
        proc = self._hook(tmp_path)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 violations" in proc.stdout

    def test_config_wires_the_script(self):
        cfg = open(os.path.join(_REPO, ".pre-commit-config.yaml")).read()
        assert "scripts/precommit-lint" in cfg
        assert os.access(self._SCRIPT, os.X_OK), (
            "scripts/precommit-lint must be executable"
        )

    def test_documented_symlink_install(self, tmp_path):
        """Review regression: the documented
        ``ln -s ../../scripts/precommit-lint .git/hooks/pre-commit``
        install runs the hook as .git/hooks/pre-commit, where the old
        script-relative cd landed in .git/ and rejected every commit.
        Git runs hooks with cwd = repo toplevel; drill exactly that."""
        import shutil
        import subprocess
        import sys

        pkg, violation = self._git_repo(tmp_path)
        scripts = tmp_path / "scripts"
        scripts.mkdir()
        shutil.copy(self._SCRIPT, scripts / "precommit-lint")
        hook = tmp_path / ".git" / "hooks" / "pre-commit"
        hook.symlink_to("../../scripts/precommit-lint")

        def run_hook():
            env = dict(os.environ)
            env.pop("PRECOMMIT_ROOT", None)  # the real install has none
            env["PYTHON"] = sys.executable
            env["PYTHONPATH"] = _REPO + os.pathsep + env.get(
                "PYTHONPATH", ""
            )
            return subprocess.run(
                ["sh", str(hook), "pkg"],
                cwd=tmp_path,
                capture_output=True,
                text=True,
                timeout=120,
                env=env,
            )

        (pkg / "fresh.py").write_text(violation)
        proc = run_hook()
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "fresh.py" in proc.stdout
        (pkg / "fresh.py").unlink()
        proc = run_hook()
        assert proc.returncode == 0, proc.stdout + proc.stderr
