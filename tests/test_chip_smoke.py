"""chip_smoke.py off the chip: it must refuse, loudly and before any leg.

What it does ON the chip is proven by running it there (CHANGES.md, PR 21);
a CPU run of its legs would be a number from the wrong machine."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_no_tpu_exits_nonzero_before_any_leg():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=env, cwd=REPO, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    lines = out.stdout.strip().splitlines()
    # what JAX found is the first and only thing on stdout
    assert len(lines) == 1 and "platform=cpu" in lines[0], out.stdout
    assert "leg " not in out.stdout and '"ok"' not in out.stdout
    assert "no TPU" in out.stderr
