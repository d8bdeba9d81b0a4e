"""Plain float32 reference for a decoder-only transformer with grouped-
query attention, rotary positions and a GELU MLP (StarCoder2's block as
the program implements it), and the seeded weights both sides use.

Written from the published description in straightforward `jax.numpy`,
importing nothing of the program.  Departures from the published model
that the program makes, and this reference follows, are listed under
`assumed` in the configuration file: RMSNorm in place of LayerNorm, and
no output-projection bias.  The q/k/v biases are applied when the
configuration sets `qkv_bias`.

`quant="int8"` or `"fp8"` computes every weight GEMM on operands of that
precision (`onchip_bench/lowp.py`): the controls that a lower precision
than the configuration's bfloat16 must fail.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from onchip_bench import lowp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

#: query and key weights drawn 1.5 times as wide as the others, so that
#: each position attends to a few others rather than to the average of
#: its context (see `make_weights`)
QK_GAIN = {"wq": 1.5, "wk": 1.5}


def _shapes(s: dict) -> dict:
    d, hd, L = s["d_model"], s["head_dim"], s["n_layers"]
    h, kv, f = s["n_heads"], s["n_kv_heads"], s["d_ff"]
    return {"ln1": (L, d), "ln2": (L, d), "wq": (L, d, h * hd),
            "wk": (L, d, kv * hd), "wv": (L, d, kv * hd),
            "wo": (L, h * hd, d), "w_up": (L, d, f), "w_down": (L, f, d),
            "mb_up": (L, f), "mb_down": (L, d)} | (
                {"bq": (L, h * hd), "bk": (L, kv * hd), "bv": (L, kv * hd)}
                if s.get("qkv_bias") else {})


def make_weights(s: dict, seed32: int):
    """The served parameter tree, made on the device in one call.

    Drawn so that greedy decoding depends on the context, which the check
    needs: with plain random weights every position shares one large
    component (the GELU's positive mean, carried to the residual by
    `w_down`, and the context's average through diffuse attention), one
    token wins at every position by a margin that no rounding flips, and
    no precision can be told from another.  So each column of `w_down`
    sums to zero over its input, and q and k are drawn wider (`QK_GAIN`).
    """
    dtype = jnp.dtype(s["dtype"])
    shapes = _shapes(s)

    def init(key):
        keys = jax.random.split(key, len(shapes) + 3)
        layers = {}
        for k, (name, shp) in zip(keys, sorted(shapes.items())):
            if len(shp) == 3:
                scale = shp[1] ** -0.5 * QK_GAIN.get(name, 1.0)
            else:  # norm gains and biases: small, so both paths use them
                scale = 0.05
            w = jax.random.normal(k, shp, F32) * scale
            if name == "w_down":
                w = w - jnp.mean(w, axis=1, keepdims=True)
            layers[name] = w.astype(dtype)
        d, v = s["d_model"], s["vocab"]
        return {"embed": (jax.random.normal(keys[-3], (v, d), dtype)
                          * 0.02).astype(dtype),
                "final_norm": (jax.random.normal(keys[-2], (d,), dtype)
                               * 0.05).astype(dtype),
                "lm_head": (jax.random.normal(keys[-1], (d, v), dtype)
                            * 0.02).astype(dtype),
                "layers": layers}

    return jax.jit(init)(jax.random.key(seed32))


def _mm(x, w, quant):
    return lowp.matmul(x, w, quant)


def _rms(x, g, eps=1e-6):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(F32))


def _rope(x, theta):
    """x (T, heads, hd): rotate-half rotary embedding at positions 0..T-1."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _layer(s: dict, quant, h, layers, i):
    lp = {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
          for k, v in layers.items()}
    t = h.shape[0]
    H, KV, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    x = _rms(h, lp["ln1"])

    def proj(name):
        y = _mm(x, lp["w" + name], quant)
        bias = lp.get("b" + name)
        return y if bias is None else y + bias.astype(F32)

    q = _rope(proj("q").reshape(t, H, hd), s["rope_theta"])
    k = _rope(proj("k").reshape(t, KV, hd), s["rope_theta"])
    v = proj("v").reshape(t, KV, hd)
    q = q.reshape(t, KV, H // KV, hd)
    scores = jnp.einsum("tkgd,ukd->kgtu", q, k, precision=HIGHEST) \
        * hd ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("kgtu,ukd->tkgd", p, v, precision=HIGHEST)
    h = h + _mm(o.reshape(t, H * hd), lp["wo"], quant)
    x = _rms(h, lp["ln2"])
    up = jax.nn.gelu(_mm(x, lp["w_up"], quant) + lp["mb_up"].astype(F32),
                     approximate=True)
    return h + _mm(up, lp["w_down"], quant) + lp["mb_down"].astype(F32)


def _head(s: dict, quant, h, final_norm, lm_head, targets):
    """Per position: the best logit, the target's logit and the argmax."""
    logits = _mm(_rms(h, final_norm), lm_head, quant)
    best = jnp.max(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return best, tgt, jnp.argmax(logits, axis=-1).astype(jnp.int32)


class Reference:
    """Teacher-forced forward over whole sequences padded to `length`
    (one compile per precision), one layer per call so that it fits."""

    def __init__(self, s: dict, length: int):
        self.s, self.length = s, length
        self._embed = jax.jit(lambda e, t: jnp.take(e, t, axis=0).astype(F32))
        self._layer = jax.jit(lambda q, h, ly, i: _layer(s, q, h, ly, i),
                              static_argnums=0)
        self._head = jax.jit(lambda q, h, fn, w, tg: _head(s, q, h, fn, w, tg),
                             static_argnums=0)

    def run(self, params, inputs: np.ndarray, targets: np.ndarray,
            quant=None):
        """inputs, targets (T,) -> (best logit, logit of the target,
        argmax), each (T,), at every input position."""
        n = inputs.shape[0]
        if n > self.length:
            raise ValueError(f"sequence {n} exceeds reference length "
                             f"{self.length}")
        tok = np.zeros(self.length, np.int32)
        tok[:n] = inputs
        tgt = np.zeros(self.length, np.int32)
        tgt[:n] = targets
        h = self._embed(params["embed"], jnp.asarray(tok))
        for i in range(self.s["n_layers"]):
            h = self._layer(quant, h, params["layers"], i)
        out = self._head(quant, h, params["final_norm"], params["lm_head"],
                         jnp.asarray(tgt))
        return tuple(np.asarray(a)[:n] for a in out)

    def run_batch(self, params, pairs, quant=None):
        """`run` over each (inputs, targets) pair in turn."""
        return [self.run(params, i, t, quant) for i, t in pairs]
