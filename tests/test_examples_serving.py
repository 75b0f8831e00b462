"""examples/serving_features.py is the user-facing tour of the serving
pillar set; it must keep running as the engine evolves (each pillar it
drives is individually proven elsewhere — this is the integration
smoke over the PUBLIC api surface)."""

import os
import subprocess
import sys


def test_serving_features_example_runs():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, os.path.join(repo, "examples",
                                      "serving_features.py")],
        env={**os.environ, "PYTHONPATH": os.pathsep.join(sys.path)},
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    for marker in ("1. per_row", "2. prefix", "3. constrained",
                   "4. cancel", "5. int8"):
        assert marker in p.stdout, (marker, p.stdout)
