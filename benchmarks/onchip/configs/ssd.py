"""Plain float32 reference for Mamba-2 (the SSD recurrence, one token at a
time), and the seeded weights both sides use.

Written from the published description (arXiv:2405.21060) in
straightforward `jax.numpy`, importing nothing of the program: per layer
an RMSNorm, one input projection split into gate z, input x, B, C and
dt; a causal depthwise convolution with SiLU over (x, B, C); the
recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t +
D x_t; a gated RMSNorm of y * silu(z); the output projection and the
residual.  One group of B and C is shared by all heads (ngroups 1).

`quant="int8"` or `"fp8"` computes the weight GEMMs (both projections
and the head) on operands of that precision (`onchip_bench/lowp.py`):
the controls that a lower precision than bfloat16 must fail.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from onchip_bench import lowp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _dims(s: dict):
    d_in = s["ssm_expand"] * s["d_model"]
    h = s["ssm_heads"]
    n = s["ssm_state"]
    return d_in, h, d_in // h, n, d_in + 2 * n


def make_weights(s: dict, seed32: int):
    """The served parameter tree, made on the device in one call."""
    dtype = jnp.dtype(s["dtype"])
    d, L, v, w = s["d_model"], s["n_layers"], s["vocab"], s["conv_width"]
    d_in, h, p, n, conv_ch = _dims(s)
    proj = 2 * d_in + 2 * n + h

    def init(key):
        k = jax.random.split(key, 12)
        nrm = jax.random.normal
        dt = jnp.exp(jax.random.uniform(k[0], (L, h), F32, np.log(1e-3),
                                        np.log(1e-1)))
        layers = {
            "ln": (nrm(k[1], (L, d), dtype) * 0.05).astype(dtype),
            "in_proj": (nrm(k[2], (L, d, proj), dtype) * d ** -0.5
                        ).astype(dtype),
            "conv_w": (nrm(k[3], (L, w, conv_ch), dtype) * 0.3
                       ).astype(dtype),
            "conv_b": (nrm(k[4], (L, conv_ch), dtype) * 0.05).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(k[5], (L, h), F32, 1.0,
                                                16.0)),
            "D": 1.0 + 0.1 * nrm(k[6], (L, h), F32),
            # inverse softplus of dt drawn log-uniform in [1e-3, 1e-1]
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm_gate": (nrm(k[7], (L, d_in), dtype) * 0.05).astype(dtype),
            "out_proj": (nrm(k[8], (L, d_in, d), dtype) * d_in ** -0.5
                         ).astype(dtype),
        }
        return {"embed": (nrm(k[9], (v, d), dtype) * 0.02).astype(dtype),
                "final_norm": (nrm(k[10], (d,), dtype) * 0.05).astype(dtype),
                "lm_head": (nrm(k[11], (d, v), dtype) * 0.02).astype(dtype),
                "layers": layers}

    return jax.jit(init)(jax.random.key(seed32))


def _mm(x, w, quant):
    return lowp.matmul(x, w, quant)


def _rms(x, g, eps=1e-6):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(F32))


def _layer(s: dict, quant, hs, layers, i):
    """hs (B, T, d) -> (B, T, d) through layer i."""
    lp = {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
          for k, v in layers.items()}
    d_in, H, P, N, conv_ch = _dims(s)
    bsz, t, _ = hs.shape
    x = _rms(hs, lp["ln"])
    zxbcdt = _mm(x.reshape(bsz * t, -1), lp["in_proj"], quant
                 ).reshape(bsz, t, -1)
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + conv_ch]
    dt = zxbcdt[..., d_in + conv_ch:]
    width = lp["conv_w"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    conv = lp["conv_b"].astype(F32) + sum(
        padded[:, j:j + t] * lp["conv_w"][j].astype(F32)
        for j in range(width))
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :d_in].reshape(bsz, t, H, P)
    Bm = xbc[..., d_in:d_in + N]
    Cm = xbc[..., d_in + N:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])              # (B, T, H)
    A = -jnp.exp(lp["A_log"])                              # (H,)

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp       # (B,H,P) (B,N) (B,N) (B,H)
        decay = jnp.exp(dt_t * A)
        state = state * decay[..., None, None] + \
            (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        y = jnp.einsum("bhpn,bn->bhp", state, c_t, precision=HIGHEST)
        return state, y

    seq = (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(Bm, 1, 0),
           jnp.moveaxis(Cm, 1, 0), jnp.moveaxis(dt, 1, 0))
    state0 = jnp.zeros((bsz, H, P, N), F32)
    _, ys = jax.lax.scan(step, state0, seq)
    y = jnp.moveaxis(ys, 0, 1) + lp["D"][None, None, :, None] * xs
    y = y.reshape(bsz, t, d_in) * jax.nn.silu(z)
    y = _rms(y, lp["norm_gate"])
    out = _mm(y.reshape(bsz * t, d_in), lp["out_proj"], quant)
    return hs + out.reshape(bsz, t, -1)


def _head(quant, h, final_norm, lm_head, targets):
    """Per position: the best logit, the target's logit and the argmax."""
    bsz, t, d = h.shape
    logits = _mm(_rms(h, final_norm).reshape(bsz * t, d), lm_head, quant
                 ).reshape(bsz, t, -1)
    best = jnp.max(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return best, tgt, jnp.argmax(logits, axis=-1).astype(jnp.int32)


class Reference:
    """Teacher-forced forward over sequences padded to `length`, a batch
    of `batch` sequences per call and one layer per call, so that it fits
    and compiles once per precision."""

    def __init__(self, s: dict, length: int, batch: int = 8):
        self.s, self.length, self.batch = s, length, batch
        self._embed = jax.jit(lambda e, t: jnp.take(e, t, axis=0).astype(F32))
        self._layer = jax.jit(lambda q, h, ly, i: _layer(s, q, h, ly, i),
                              static_argnums=0)
        self._head = jax.jit(_head, static_argnums=0)

    def run_batch(self, params, pairs, quant=None):
        """Up to `batch` (inputs, targets) pairs, each (T_i,) -> (best
        logit, logit of the target, argmax), each (T_i,)."""
        if len(pairs) > self.batch:
            raise ValueError("more sequences than the reference batch")
        tok = np.zeros((self.batch, self.length), np.int32)
        tgt = np.zeros((self.batch, self.length), np.int32)
        for r, (inp, tg) in enumerate(pairs):
            if inp.shape[0] > self.length:
                raise ValueError(f"sequence {inp.shape[0]} exceeds "
                                 f"reference length {self.length}")
            tok[r, :inp.shape[0]] = inp
            tgt[r, :inp.shape[0]] = tg
        h = self._embed(params["embed"], jnp.asarray(tok))
        for i in range(self.s["n_layers"]):
            h = self._layer(quant, h, params["layers"], i)
        res = []
        for r, (inp, _) in enumerate(pairs):   # one row at a time: fits
            out = self._head(quant, h[r:r + 1], params["final_norm"],
                             params["lm_head"], jnp.asarray(tgt[r:r + 1]))
            res.append(tuple(np.asarray(a)[0, :inp.shape[0]] for a in out))
        return res
