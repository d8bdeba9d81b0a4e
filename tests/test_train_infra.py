"""Training infrastructure: optimizers, checkpointing, fault tolerance,
synthetic data, end-to-end loss decrease."""

import os
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import synthetic
from repro.train import checkpoint as ckpt
from repro.train import fault
from repro.train import optimizer as opt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- optimizer -----------------------------------------------------------------

def _quad_problem():
    rng = np.random.default_rng(0)
    target = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    params = {"w": jnp.zeros((32, 64), jnp.float32)}

    def grads(p):
        return {"w": p["w"] - target}

    return params, grads, target


@pytest.mark.parametrize("kind,kw", [
    ("adamw", {"moment_dtype": "f32"}),
    ("adamw", {"moment_dtype": "bf16"}),
    ("adamw", {"moment_dtype": "int8"}),
    ("adafactor", {}),
])
def test_optimizer_converges_on_quadratic(kind, kw):
    params, grads, target = _quad_problem()
    init, update = opt.make_optimizer(
        kind, lr=0.05, total_steps=300, warmup_steps=10, weight_decay=0.0,
        **kw)
    st = init(params)
    for _ in range(300):
        params, st = update(params, grads(params), st)
    err = float(jnp.abs(params["w"] - target).mean())
    assert err < 0.15, err


def test_quantized_moments_close_to_f32():
    params, grads, _ = _quad_problem()
    outs = {}
    for md in ("f32", "int8"):
        p = dict(params)
        init, update = opt.make_optimizer("adamw", lr=0.05, total_steps=100,
                                          warmup_steps=5, weight_decay=0.0,
                                          moment_dtype=md)
        st = init(p)
        for _ in range(50):
            p, st = update(p, grads(p), st)
        outs[md] = np.asarray(p["w"])
    rel = np.abs(outs["int8"] - outs["f32"]).mean() / \
        (np.abs(outs["f32"]).mean() + 1e-9)
    assert rel < 0.05, rel


def test_grad_clip_applies():
    params = {"w": jnp.zeros((4,), jnp.float32)}
    init, update = opt.make_optimizer("adamw", lr=1e-3, total_steps=10,
                                      warmup_steps=0)
    st = init(params)
    big = {"w": jnp.full((4,), 1e6, jnp.float32)}
    p2, _ = update(params, big, st)
    assert np.isfinite(np.asarray(p2["w"])).all()
    assert np.abs(np.asarray(p2["w"])).max() < 1.0


def test_lr_schedule():
    lrs = [float(opt.warmup_cosine(jnp.asarray(s), 1.0, 10, 100))
           for s in [0, 5, 10, 55, 100]]
    assert lrs[0] == 0.0
    assert lrs[1] == pytest.approx(0.5)
    assert lrs[2] == pytest.approx(1.0, abs=0.01)
    assert lrs[3] < lrs[2]
    assert lrs[4] == pytest.approx(0.1, abs=0.02)


# --- checkpointing --------------------------------------------------------------

def _small_state():
    return {"params": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                       "b": jnp.ones((4,), jnp.bfloat16)},
            "step": jnp.asarray(7, jnp.int32)}


def test_checkpoint_roundtrip_and_prune():
    with tempfile.TemporaryDirectory() as d:
        mgr = ckpt.CheckpointManager(d, keep_last=2)
        state = _small_state()
        for s in (1, 2, 3, 4):
            mgr.save(state, s)
        assert mgr.all_steps() == [3, 4]
        restored, at = mgr.restore(jax.eval_shape(lambda: state))
        assert at == 4
        np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                      np.asarray(state["params"]["w"]))
        assert restored["params"]["b"].dtype == jnp.bfloat16


def test_checkpoint_async_and_atomic():
    with tempfile.TemporaryDirectory() as d:
        mgr = ckpt.CheckpointManager(d)
        mgr.save(_small_state(), 1, blocking=False)
        mgr.wait()
        assert mgr.latest_step() == 1
        # a stale .tmp dir must be ignored
        os.makedirs(os.path.join(d, "step_00000099.tmp"))
        assert mgr.latest_step() == 1


def test_checkpoint_corruption_falls_back():
    with tempfile.TemporaryDirectory() as d:
        mgr = ckpt.CheckpointManager(d)
        state = _small_state()
        mgr.save(state, 1)
        mgr.save(state, 2)
        # corrupt the newest payload
        p = os.path.join(d, "step_00000002", "proc_0.msgpack.zst")
        with open(p, "wb") as f:
            f.write(b"garbage")
        restored, at = mgr.restore(jax.eval_shape(lambda: state))
        assert at == 1


# --- fault tolerance --------------------------------------------------------------

def test_straggler_watchdog():
    wd = fault.StragglerWatchdog(factor=3.0, min_samples=3)
    for s in range(6):
        assert not wd.observe(s, 0.10)
    assert wd.observe(6, 0.50)
    assert wd.flagged == [6]
    assert not wd.observe(7, 0.12)


def test_straggler_watchdog_injectable_clock():
    """step_start/step_end on an injected clock: detection is a pure
    function of the fed timestamps (the fleet's virtual-tick clock uses
    exactly this hook), no wall time involved."""
    t = {"now": 0.0}
    wd = fault.StragglerWatchdog(factor=3.0, min_samples=3,
                                 clock=lambda: t["now"])
    for s in range(5):
        wd.step_start()
        t["now"] += 1.0
        assert not wd.step_end(s)
    wd.step_start()
    t["now"] += 10.0                      # 10x median -> flagged
    assert wd.step_end(5)
    assert wd.flagged == [5]
    # replay with the same fed durations is bit-identical
    wd2 = fault.StragglerWatchdog(factor=3.0, min_samples=3,
                                  clock=lambda: t["now"])
    for s, d in enumerate([1.0] * 5 + [10.0]):
        wd2.observe(s, d)
    assert wd2.flagged == wd.flagged


def test_run_with_restarts_injectable_sleep():
    """The supervisor's backoff goes through the injected sleep (linear
    in the attempt), so deterministic tests never wall-wait."""
    slept = []

    def main(attempt):
        if attempt < 2:
            raise RuntimeError("boom")
        return attempt

    assert fault.run_with_restarts(main, max_restarts=3,
                                   sleep=slept.append) == 2
    assert slept == pytest.approx([0.1, 0.2])


def test_preemption_guard_flag():
    g = fault.PreemptionGuard()
    assert not g.preempted
    g.request()
    assert g.preempted


def test_run_with_restarts():
    calls = []

    def main(attempt):
        calls.append(attempt)
        if attempt < 2:
            raise RuntimeError("boom")
        return 42

    assert fault.run_with_restarts(main, max_restarts=3) == 42
    assert calls == [0, 1, 2]


def test_crash_restart_resumes_training():
    """Kill a real training run mid-flight; the restart must resume from the
    checkpoint (same CLI, same dir) and finish all steps."""
    with tempfile.TemporaryDirectory() as d:
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        args = [sys.executable, "-m", "repro.launch.train",
                "--arch", "tinyllama-1.1b", "--reduced", "--steps", "30",
                "--batch", "4", "--seq", "64", "--ckpt-dir", d,
                "--ckpt-every", "5", "--log-every", "5"]
        proc = subprocess.Popen(args, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        # wait until at least one checkpoint lands, then kill hard
        deadline = time.time() + 600
        while time.time() < deadline:
            steps = ckpt.CheckpointManager(d).all_steps()
            if steps:
                break
            if proc.poll() is not None:
                break
            time.sleep(1.0)
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        steps = ckpt.CheckpointManager(d).all_steps()
        assert steps, "no checkpoint was written before the kill"
        # restart: must resume and complete
        out = subprocess.run(args, env=env, capture_output=True, text=True,
                             timeout=900)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "resumed from step" in out.stdout
        assert "done:" in out.stdout


# --- data -------------------------------------------------------------------------

def test_lm_batch_deterministic_and_sharded():
    a = synthetic.lm_batch(100, 8, 32, step=3, seed=1)
    b = synthetic.lm_batch(100, 8, 32, step=3, seed=1)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = synthetic.lm_batch(100, 8, 32, step=4, seed=1)
    assert not np.array_equal(a["tokens"], c["tokens"])
    # per-host disjoint shards
    h0 = synthetic.lm_batch(100, 8, 32, step=3, seed=1, process_index=0,
                            process_count=2)
    h1 = synthetic.lm_batch(100, 8, 32, step=3, seed=1, process_index=1,
                            process_count=2)
    assert h0["tokens"].shape == (4, 32)
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_labels_are_shifted_tokens():
    b = synthetic.lm_batch(50, 2, 16, step=0)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
    assert b["mask"][:, -1].sum() == 0


def test_shapes_classification_learnable_structure():
    x, y = synthetic.shapes_classification(64, image=16)
    assert x.shape == (64, 16, 16, 3)
    assert set(np.unique(y)) <= {0, 1, 2, 3}
    # classes differ in mean image statistics (the blob)
    m0 = x[y == 0].mean(axis=0)
    m1 = x[y == 1].mean(axis=0) if (y == 1).any() else m0
    assert np.abs(m0 - m1).max() > 0.3


def test_restart_of_finished_run_exits_cleanly():
    """A restart that resumes from the last step's checkpoint has no step
    left to run; it must still finish (the crash-restart test hits this
    when the first run completes before it is killed)."""
    with tempfile.TemporaryDirectory() as d:
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        args = [sys.executable, "-m", "repro.launch.train",
                "--arch", "tinyllama-1.1b", "--reduced", "--steps", "2",
                "--batch", "2", "--seq", "32", "--ckpt-dir", d,
                "--ckpt-every", "1"]
        for _ in range(2):
            out = subprocess.run(args, env=env, capture_output=True,
                                 text=True, timeout=600)
            assert out.returncode == 0, out.stdout + out.stderr
        assert "resumed from step 2" in out.stdout
        assert "done: no steps left" in out.stdout
