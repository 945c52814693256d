"""The four-chip cell: sound on four CPU devices, and not correct with the
exchange between chips left out."""
import json
import os
import subprocess
import sys
from pathlib import Path


def test_exchange_left_out_is_not_correct():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run([sys.executable, str(Path(__file__).with_name("four_chip_run.py"))],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(out["cores"]) > 1  # tables on more than one chip, so the exchange carries them
    assert out["sound"]["value"] <= out["sound"]["limit"]
    assert out["broken"]["value"] > out["broken"]["limit"]
