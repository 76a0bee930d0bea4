"""End-to-end behaviour: DP training decreases loss; checkpoint/restart
reproduces the uninterrupted run; serving generates deterministically."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.launch import train as train_mod
from repro.launch import serve as serve_mod


@pytest.mark.slow
def test_dp_training_decreases_loss(tmp_path):
    losses = train_mod.main([
        "--arch", "llama3.2-1b", "--steps", "40", "--batch", "16",
        "--seq", "64", "--lr", "1e-2", "--clip", "1.0", "--noise", "0.1",
        "--strategy", "ghost"]).losses
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05


@pytest.mark.slow
def test_restart_reproduces_run(tmp_path):
    """A run interrupted at step 15 and restarted from its checkpoint ends
    with the same loss as an uninterrupted run (determinism contract)."""
    common = ["--arch", "llama3.2-1b", "--steps", "24", "--batch", "4",
              "--seq", "32", "--strategy", "bk", "--ckpt-every", "8"]
    a = train_mod.main(common + ["--ckpt-dir", str(tmp_path / "a")]).losses
    b = train_mod.main(common + ["--ckpt-dir", str(tmp_path / "b"),
                                 "--fail-at", "15"]).losses
    assert abs(a[-1] - b[-1]) < 1e-4


@pytest.mark.slow
def test_cnn_dp_training(tmp_path):
    losses = train_mod.main([
        "--arch", "alexnet", "--steps", "25", "--batch", "8",
        "--lr", "2e-3", "--strategy", "crb"]).losses
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


@pytest.mark.slow
def test_serving_runs(capsys):
    serve_mod.main(["--arch", "llama3.2-1b", "--n-requests", "4",
                    "--batch", "2", "--prompt-len", "8", "--gen", "4"])
    out = capsys.readouterr().out
    assert "served 4 requests" in out
