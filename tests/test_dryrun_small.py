"""Dry-run flow on a shrunken fake fleet (subprocess so XLA device-count
forcing can't leak into other tests)."""
import json
import subprocess
import sys
from pathlib import Path

import pytest


def _run(args, env_extra, cwd=Path(__file__).resolve().parents[1]):
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.update(env_extra)
    return subprocess.run([sys.executable, "-m", "repro.launch.dryrun",
                           *args], capture_output=True, text=True, cwd=cwd,
                          env=env, timeout=900)


@pytest.mark.slow
def test_dryrun_cell_small_mesh(tmp_path):
    out = _run(["--arch", "gemma3-1b", "--shape", "decode_32k",
                "--mesh", "single", "--out", str(tmp_path)],
               {"REPRO_DRYRUN_DEVICES": "4", "REPRO_DRYRUN_MESH": "2x2"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    rec = json.loads(
        (tmp_path / "gemma3-1b__decode_32k__single.json").read_text())
    assert rec["ok"]
    assert rec["hlo"]["flops"] > 0
    assert rec["hlo"]["num_partitions"] == 4
    assert rec["model_flops"] > 0


@pytest.mark.slow
def test_dryrun_multipod_small_mesh(tmp_path):
    out = _run(["--arch", "gemma3-1b", "--shape", "decode_32k",
                "--mesh", "multi", "--out", str(tmp_path)],
               {"REPRO_DRYRUN_DEVICES": "8", "REPRO_DRYRUN_MESH": "2x2x2"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    rec = json.loads(
        (tmp_path / "gemma3-1b__decode_32k__multi.json").read_text())
    assert rec["ok"]
    assert rec["hlo"]["num_partitions"] == 8
