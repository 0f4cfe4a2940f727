"""Short CI smoke of the randomized composition soak (tools/soak.py).

The full runs (150s × {tcp+shaped, shm, uds}: 25k+ rounds, 1000+
elastic resizes, device codecs + rowsparse + async mixed throughout)
are run by hand with tools/soak.py; CI keeps a seeded 8-second slice
alive so the harness itself cannot rot.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_soak_smoke():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "soak.py"),
         "--seconds", "8", "--seed", "11"],
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "SOAK OK" in out.stdout
