"""chip_smoke.py refuses to run anywhere but on a TPU: on the CPU it exits
non-zero within seconds, names the missing chip and prints no result line
— so no later change can let it fall back to the CPU and pass. Copied
alone into an empty directory it fails too (it needs the repo's src/)."""
import os
import pathlib
import shutil
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(script: pathlib.Path, **env):
    return subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, timeout=120, cwd=script.parent,
                          env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def _no_result(out):
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_chip_smoke_fails_without_a_chip():
    out = _run(REPO / "chip_smoke.py")
    _no_result(out)
    assert "no TPU chip" in out.stderr and "'cpu'" in out.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(REPO / "chip_smoke.py", alone)
    out = _run(alone, PYTHONPATH="")
    _no_result(out)
    assert "src/" in out.stderr
