"""``chip_smoke.py`` rehearsed at a toy size on the CPU.

The chip run itself needs the chip; what can be held here is everything
around it: each phase's control flow (the kill and the resume, the
server's answers, the four-device path on virtual devices), the exact
shape of the last line, the exit codes, and that the parent stays off
JAX. One rehearsal per mode is shared by the tests that read it.
"""

import ast
import json
import os
import shutil
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _run(args, env=None, cwd=_REPO, script=_SMOKE, timeout=600):
    full = dict(os.environ, JAX_PLATFORMS="cpu")
    # the rehearsal's four-device mode sets its own device count
    full.pop("XLA_FLAGS", None)
    for key, value in (env or {}).items():
        if value is None:
            full.pop(key, None)
        else:
            full[key] = value
    return subprocess.run(
        [sys.executable, script, *args],
        cwd=cwd, env=full, capture_output=True, text=True, timeout=timeout,
    )


@pytest.fixture(scope="module")
def one_chip():
    proc = _run(["--tiny"])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc.stdout.strip().splitlines()


@pytest.fixture(scope="module")
def four_chips():
    proc = _run(["--tiny", "--chips", "4"])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc.stdout.strip().splitlines()


def test_last_line_is_exactly_the_contract(one_chip):
    assert one_chip[-1] == (
        '{"ok": true, "device": {"platform": "cpu", "kind": "cpu", '
        '"count": 1}}'
    )


def test_train_phase_kills_once_and_resumes_from_the_staged_step(one_chip):
    text = "\n".join(one_chip)
    assert text.count("train: SIGKILL worker pid") == 1
    (line,) = [l for l in one_chip if l.startswith("train: staged step")]
    words = line.split()
    staged, resumed = int(words[3]), int(words[words.index("from") + 2])
    assert staged == resumed == 3
    first = [l for l in one_chip if l.startswith("train: losses first start")]
    after = [l for l in one_chip if l.startswith("train: losses after resume")]
    assert first and after and f"[{resumed + 1}]" in after[0]
    assert "kill to first resumed step" in text
    assert "second start hit the compile cache" in text
    assert "interposer /metrics" in text


def test_serve_phase_answers_every_request(one_chip):
    text = "\n".join(one_chip)
    assert "serve: plain completion, 8 tokens" in text
    assert "streamed completion" in text and "equal to the plain one" in text
    assert "burst of 6 answered with [4, 7, 10, 13, 16, 19] tokens" in text
    assert "port" in text and "no /dev/shm segment left" in text


def test_four_chip_phase_runs_only_the_mesh_path(four_chips):
    text = "\n".join(four_chips)
    assert four_chips[-1] == (
        '{"ok": true, "device": {"platform": "cpu", "kind": "cpu", '
        '"count": 4}}'
    )
    assert "train:" not in text and "serve:" not in text
    assert "mesh: ONE worker pid" in text and "sees 4 x cpu" in text
    assert "within the stated bf16 band" in text
    assert "shards on four distinct devices" in text
    assert "equal to the host copy" in text and "'tp': 2" in text


def test_parent_never_imports_jax():
    """Statically: no import of jax (or of the package, which pulls it)
    anywhere in the parent. At run time the script asserts it itself —
    the rehearsals above passed through that assertion."""
    tree = ast.parse(open(_SMOKE).read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {alias.name.split(".")[0] for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert not imported & {"jax", "jaxlib", "flax", "dlrover_tpu"}, imported
    assert 'assert "jax" not in sys.modules' in open(_SMOKE).read()


def test_no_accelerator_means_no_result_line():
    """As the driver runs it in a sandbox: the platform held to the CPU,
    no ``--tiny``. Non-zero exit and NOTHING on stdout to parse — and
    the native build is left alone: the tests of the interposer run
    beside this one, on another worker, from the same checkout."""
    product = os.path.join(_REPO, "native", "tpu_timer", "test_tsan")
    made = not os.path.exists(product)
    if made:
        open(product, "w").close()
    try:
        proc = _run([])
        assert os.path.exists(product)
    finally:
        if made:
            os.unlink(product)
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""
    assert "No result" in proc.stderr


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    lone = shutil.copy(_SMOKE, tmp_path / "chip_smoke.py")
    proc = _run(["--tiny"], cwd=str(tmp_path), script=str(lone))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_tiny_is_only_honoured_with_the_cpu_pinned():
    proc = _run(["--tiny"], env={"JAX_PLATFORMS": None})
    assert proc.returncode == 2 and proc.stdout.strip() == ""


_PATCHED = """
import sys
sys.path.insert(0, {repo!r})
import chip_smoke
{patch}
sys.exit(chip_smoke.main(["--tiny"]))
"""


def _run_patched(patch):
    proc = subprocess.run(
        [sys.executable, "-c", _PATCHED.format(repo=_REPO, patch=patch)],
        cwd=_REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    lines = proc.stdout.strip().splitlines()
    result = lines and lines[-1].startswith("{")
    return proc, (json.loads(lines[-1]) if result else None)


def test_a_phase_that_fails_exits_nonzero_with_ok_false():
    proc, last = _run_patched(
        "def boom(*a, **k): raise chip_smoke.PhaseFailed('planted')\n"
        "chip_smoke.phase_train = boom"
    )
    assert proc.returncode == 1
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert "planted" in proc.stderr


def test_an_unexpected_exception_is_not_carried_past():
    proc, last = _run_patched(
        "def boom(*a, **k): raise KeyError('not a phase verdict')\n"
        "chip_smoke.phase_train = boom"
    )
    assert proc.returncode != 0 and last is None
    assert "KeyError" in proc.stderr


def test_a_child_on_another_platform_fails_its_phase():
    """Require the chip while the children are held to the CPU: the
    server starts (the caller's pin is honoured), says ``cpu`` on
    /healthz, and the phase fails on exactly that."""
    proc, last = _run_patched(
        "chip_smoke.phase_train = lambda *a: "
        "{'platform': 'cpu', 'kind': 'cpu', 'count': 1}\n"
        "real_serve = chip_smoke.phase_serve\n"
        "chip_smoke.phase_serve = lambda required, tiny: "
        "real_serve('tpu', tiny)\n"
    )
    assert proc.returncode == 1 and last["ok"] is False
    assert "server saw 'cpu', required 'tpu'" in proc.stderr


def test_no_segment_of_the_run_is_left_in_dev_shm(one_chip, four_chips):
    """The flash checkpoint outlives the agent by design; a smoke run's
    must not outlive the smoke (1.5 GB of /dev/shm a run on the chip,
    and stale segments slow every later checkpoint test here)."""
    import glob

    assert glob.glob("/dev/shm/dlrover_smoke_*") == []
