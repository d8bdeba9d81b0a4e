"""Accuracy-drop calibration (the ApproxTrain step of the methodology):
train a small CNN on the synthetic shapes task, then measure real top-1
accuracy under each approximate multiplier.  This grounds the GA's
NMED->drop proxy (core/ga.py) in measured data."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.approx import gemm as G
from repro.core import ga as gamod
from repro.core import multipliers as mm
from repro.data import synthetic
from repro.models import cnn


N_CLASSES = 8
TASK = dict(image=32, n_classes=N_CLASSES, amplitude=0.9, noise=0.55)


def train_small_cnn(steps: int = 260, seed: int = 0):
    x, y = synthetic.shapes_classification(512, seed=seed, **TASK)
    xt, yt = jnp.asarray(x), jnp.asarray(y)
    params = cnn.init_vgg("vgg_mini", jax.random.key(seed),
                          n_classes=N_CLASSES, image=32)

    def loss(p, xb, yb):
        logits = cnn.vgg_forward(p, xb, "vgg_mini")
        onehot = jax.nn.one_hot(yb, N_CLASSES)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))

    @jax.jit
    def step(p, xb, yb, lr):
        l, g = jax.value_and_grad(loss)(p, xb, yb)
        p = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)
        return p, l

    rng = np.random.default_rng(seed)
    for s in range(steps):
        idx = rng.integers(0, 512, 64)
        params, l = step(params, xt[idx], yt[idx], jnp.asarray(0.05))
    return params


def accuracy(params, spec, seed=1) -> float:
    x, y = synthetic.shapes_classification(512, seed=seed, **TASK)
    logits = cnn.vgg_forward(params, jnp.asarray(x), "vgg_mini", spec=spec)
    return float((jnp.argmax(logits, -1) == jnp.asarray(y)).mean())


def main() -> list[str]:
    t0 = time.time()
    params = train_small_cnn()
    base = accuracy(params, None)
    lines = [f"accuracy_exact,{(time.time() - t0) * 1e6:.0f},"
             f"top1={base:.4f}"]
    for name in ("trunc1x1", "trunc2x2", "trunc3x3", "trunc4x4"):
        mobj = mm.get_multiplier(name)
        spec = G.from_multiplier(mobj)
        t0 = time.time()
        acc = accuracy(params, spec)
        drop = 100 * (base - acc)
        proxy = gamod.proxy_accuracy_drop(mobj)
        lines.append(
            f"accuracy_{name},{(time.time() - t0) * 1e6:.0f},"
            f"top1={acc:.4f};drop_pct={drop:.2f};proxy_pct={proxy:.2f};"
            f"nmed={mobj.stats.nmed:.5f}")
    return lines


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    print("\n".join(main()))
