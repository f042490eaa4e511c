"""Integration: end-to-end training, crash/restart continuation, serving."""
import os

import numpy as np
import pytest

from repro.launch.serve import run_serving
from repro.launch.train import train


def test_train_loss_decreases(tmp_path):
    m = train("qwen2_0_5b", steps=30, batch=4, seq=64, lr=1e-3,
              ckpt_dir=None, log_every=100)
    assert m["loss_drop"] > 0.05, m


def test_crash_restart_continues_identically(tmp_path):
    """Kill at step 12, restart, final state must match an uninterrupted
    run (stateless data indexing + checkpointed optimizer ⇒ exact resume
    modulo the optimizer steps lost since the last checkpoint)."""
    d1 = str(tmp_path / "interrupted")
    with pytest.raises(KeyboardInterrupt):
        train("qwen2_0_5b", steps=20, batch=2, seq=32, ckpt_dir=d1,
              ckpt_every=5, fail_at_step=12, log_every=100)
    # restart — must resume from step 10 (last ckpt) and finish
    m1 = train("qwen2_0_5b", steps=20, batch=2, seq=32, ckpt_dir=d1,
               ckpt_every=5, log_every=100)

    d2 = str(tmp_path / "clean")
    m2 = train("qwen2_0_5b", steps=20, batch=2, seq=32, ckpt_dir=d2,
               ckpt_every=5, log_every=100)
    # identical final loss: the resumed run replays the same batches from
    # the checkpointed (params, opt) state
    np.testing.assert_allclose(m1["final_loss"], m2["final_loss"],
                               rtol=1e-5)


def test_train_with_grad_compress_converges():
    m = train("qwen2_0_5b", steps=20, batch=4, seq=32, lr=1e-3,
              grad_compress=True, log_every=100)
    assert np.isfinite(m["final_loss"])
    assert m["loss_drop"] > 0.0


@pytest.mark.parametrize("arch", ["rwkv6_3b", "gemma2_2b"])
def test_train_other_families(arch):
    m = train(arch, steps=8, batch=2, seq=32, log_every=100)
    assert np.isfinite(m["final_loss"])


def test_serving_pc_vs_serial_same_outputs():
    pc = run_serving("qwen2_0_5b", sessions=4, requests_per_session=2,
                     n_tokens=4, max_batch=4, scheduler="pc", seed=7,
                     reduced=True)
    ser = run_serving("qwen2_0_5b", sessions=4, requests_per_session=2,
                      n_tokens=4, max_batch=4, scheduler="serial", seed=7,
                      reduced=True)
    assert pc["requests"] == ser["requests"] == 8
    # combining must reduce device dispatches vs serial
    assert pc["device_steps"] <= ser["device_steps"]
