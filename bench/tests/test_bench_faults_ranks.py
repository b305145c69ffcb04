"""The multi-rank halo traffic's fault at two ranks on two virtual CPU
devices: leaving the exchange between ranks out turns ``correct``
false, where the sound program passes."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
SEED = 2**31 + 3


TWO_RANKS = textwrap.dedent("""
    import dataclasses, json, sys
    from pathlib import Path
    sys.path.insert(0, {bench!r})
    import jax, run
    from repro.comm.perfmodel import TPU_V5E
    import repro.halo.program as program
    cell = run.load_cell("halo3d-512.r1")
    cell = dataclasses.replace(
        cell, config=dict(cell.config, interior=[8, 8, 8]),
        traffic=dict(cell.traffic, decomposition=[2, 1, 1]))
    def once(tag):
        res = run.run_cell(cell, {seed}, 0.3, False, jax.devices()[:2],
                           params=TPU_V5E, state=Path({state!r}) / tag)
        print(tag, res["correct"], res["checks"]["max_rel_err"]["value"])
    once("sound")
    program.halo_exchange = lambda local, *a, **k: local
    once("no-exchange")
""")


def test_two_ranks_exchange_left_out_is_caught(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    code = TWO_RANKS.format(bench=str(BENCH), seed=SEED, state=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = dict(l.split(" ", 1) for l in proc.stdout.splitlines()
                 if l.startswith(("sound", "no-exchange")))
    assert lines["sound"].startswith("True")
    assert lines["no-exchange"].startswith("False")
