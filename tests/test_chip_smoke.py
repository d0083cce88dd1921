"""chip_smoke.py: refuses a host without a TPU, and its phases run end to
end on the CPU when the test steers them to smoke widths."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]
SMOKE_ARGS = ["--arch", "granite-moe-3b-a800m", "--smoke", "--seq", "32"]


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return dict(env, **extra)


@pytest.mark.parametrize("alone", [False, True], ids=["checkout", "alone"])
def test_refuses_without_a_tpu(alone, tmp_path):
    """On the CPU (and in a directory holding nothing else of the repo) it
    exits non-zero and prints no result."""
    if alone:
        shutil.copy(REPO / "chip_smoke.py", tmp_path)
    cwd = tmp_path if alone else REPO
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=_cpu_env(), capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs 1 TPU chip" in proc.stderr


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(chip_smoke, "require_tpu",
                        lambda chips: jax.devices())
    monkeypatch.setattr(chip_smoke, "MODEL_ARGS", SMOKE_ARGS)
    monkeypatch.setattr(chip_smoke, "MOE_TOKENS", 200)
    monkeypatch.setattr(chip_smoke, "CKPT_DIR", str(tmp_path / "ckpt"))
    return chip_smoke


def test_one_chip_phases_on_cpu(smoke, capsys):
    """Training at smoke widths, then the MoE layer check at granite's full
    widths (Pallas in interpret mode), then the JSON last line."""
    assert smoke.main([]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    last = json.loads(out[-1])
    assert last["ok"] is True and last["device"]["count"] == 1
    assert sum(line.startswith("moe_layer ") for line in out) == 2
    assert not os.path.exists(smoke.CKPT_DIR)


def test_moonlight_phase_on_cpu(smoke, capsys, monkeypatch):
    """Moonlight's step at smoke widths, a quarter share of its experts and
    vocabulary, through the trainer's entry point, then the JSON last
    line."""
    monkeypatch.setattr(smoke, "MOONLIGHT_ARGS", [
        "--arch", "moonlight-16b-a3b", "--smoke", "--seq", "32",
        "--expert-share", "4"])
    assert smoke.main(["--model", "moonlight"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1])["ok"] is True
    assert any(line.startswith("step    1 loss ") for line in out)
    assert not os.path.exists(smoke.CKPT_DIR)


_FOUR = """
import json, sys, jax
sys.path.insert(0, sys.argv[1])
import chip_smoke as cs
cs.require_tpu = lambda chips: jax.devices()
cs.MODEL_ARGS = json.loads(sys.argv[3])
cs.CKPT_DIR = sys.argv[2]
rc = cs.main(["--chips", "4"])
# The chip path never imports the modules that rewrite XLA_FLAGS.
assert not {"repro.launch.dryrun", "repro.launch.hillclimb"} & set(sys.modules)
sys.exit(rc)
"""


def test_four_chip_phases_on_cpu_devices(tmp_path):
    """Both EP exchanges train on a 1x4 mesh of forced host devices and
    agree on every step's loss and grad norm, without importing the dry-run
    modules."""
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR, str(REPO), str(tmp_path / "ckpt"),
         json.dumps(SMOKE_ARGS)],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4",
                     JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")))
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1])["device"]["count"] == 4
    compared = [line.split(":")[0] for line in lines
                if ": loss baseline " in line and "; grad_norm baseline " in line]
    assert compared == ["step 1", "step 2", "step 3"]
