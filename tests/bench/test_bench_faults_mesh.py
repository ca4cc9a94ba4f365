"""The engine on four chips (here four virtual CPU devices, in a process of
their own): a sound run is correct; with the exchange between chips left
out of the sharded step, `correct` comes out false."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent


@pytest.mark.parametrize("broken", [False, True])
def test_exchange_between_chips(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    args = [sys.executable, str(HERE / "mesh_run.py")]
    if broken:
        args.append("--no-exchange")
    p = subprocess.run(args, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is (not broken), line["checks"]
