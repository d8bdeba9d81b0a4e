"""Chip smoke: serve tinyllama-1.1b at full width on a TPU, end to end.

    python3 chip_smoke.py                  # one chip: three tiers + checks
    python3 chip_smoke.py --mesh model=4   # four chips: TP vs one chip only

One chip (no arguments): builds `Engine(get_config("tinyllama-1.1b"))` at
its published widths (22 layers, d_model 2048, d_ff 5632, vocab 32000,
bf16, random weights from --seed) on the default one-chip mesh with
kernel policy "auto", and serves 8 greedy requests (prompt 128, 32 new
tokens) on three multiplier tiers:

* `exact` — bf16 GEMMs; the prefill logits must be finite;
* `trunc2x2` — integer truncation; its token ids under "auto" must equal
  those of engines pinned to the XLA path and to the Pallas kernels;
* `pareto:0.02:r2` — the paper's low-rank approximate multiplier (rank 2),
  served again through the Pallas kernels; one GEMM at served width must
  come out of the fused and the skinny kernels bit-identical to the
  stacked kernel.

Each tier prints the GemmPlan its GEMMs resolved to, its compile time and
a smoke decode rate.

`--mesh model=4` runs only the tensor-parallel check: an engine on that
mesh against a one-chip engine on the first device.  Greedy tokens must
be identical on `trunc2x2` (integer GEMMs).  On `exact` (bf16) a
row-parallel reduction adds in another order than one chip's dot, so a
request may diverge, but only at a near-tie (see `check_tp_near_ties`).

Everything runs in this one process.  Any failure exits non-zero without
printing a result; the last line on success is one JSON object naming the
device.  Speeds printed here are smoke numbers, not a benchmark.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "tinyllama-1.1b"
TIERS = ("exact", "trunc2x2", "pareto:0.02:r2")
TP_TIERS = ("exact", "trunc2x2")
N_REQUESTS = 8
PROMPT_LEN = 128
NEW_TOKENS = 32


class SmokeFailure(RuntimeError):
    """A smoke check did not hold."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu() -> dict:
    """The device record of the last line; fails unless JAX's first
    device is a TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"needs a TPU; JAX found {devs[0].platform!r}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def count_cache_events() -> collections.Counter:
    """Persistent-compilation-cache hits and misses, as JAX reports them."""
    import jax

    counts: collections.Counter = collections.Counter()
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listener(event: str, **_):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listener)
    return counts


def gemm_widths(cfg) -> list[tuple[str, int, int]]:
    """(name, K, N) of the decoder's GEMMs at the config's widths."""
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    return [("q/o", cfg.d_model, cfg.n_heads * hd),
            ("k/v", cfg.d_model, cfg.n_kv_heads * hd),
            ("up/gate", cfg.d_model, cfg.d_ff),
            ("down", cfg.d_ff, cfg.d_model),
            ("lm_head", cfg.d_model, cfg.vocab)]


def tier_plans(engine, tier: str, *, prefill_m: int, decode_m: int) -> dict:
    """The GemmPlan each GEMM of `tier` resolves to, prefill and decode."""
    from repro.kernels import dispatch
    from repro.models import api

    spec = api.make_spec(engine.cfg, mult=tier)
    if spec is None:
        return {"all": "bf16 XLA dot (exact tier: no approximate GEMM)"}
    rank = spec.rank if spec.mode == "lowrank" else 0
    out = {}
    for name, k, n in gemm_widths(engine.cfg):
        for phase, m in (("prefill", prefill_m), ("decode", decode_m)):
            p = dispatch.choose_gemm_path(spec.policy, m=m, k=k, n=n,
                                          mode=spec.mode, rank=rank,
                                          n_planes=spec.n_planes)
            out[f"{name}/{phase}"] = (
                f"{p.path}{'-skinny' if p.skinny else ''} "
                f"({p.bm},{p.bk},{p.bn}) u{p.unroll} [{p.source}]")
    return out


def make_requests(vocab: int, *, n: int, prompt_len: int, new_tokens: int,
                  seed: int) -> list[tuple[str, list[int]]]:
    import numpy as np

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, vocab, (n, prompt_len))
    return [(f"r{i}", prompts[i].tolist()) for i in range(n)]


def serve(engine, tier: str, requests, *, new_tokens: int) -> dict:
    """Serve `requests` greedily on `tier`; returns tokens by request id
    plus the decode seconds and tokens this batch took."""
    from repro.serving import Request, SamplingParams

    engine.set_tier(tier)
    sp = SamplingParams(max_new_tokens=new_tokens)
    before = engine.stats()
    tag = f"{tier}#{len(engine.completions)}"   # request ids are unique
    ids = {f"{tag}/{rid}": rid for rid, _ in requests}
    for rid, prompt in requests:
        engine.submit(Request(f"{tag}/{rid}", prompt, sp))
    done = [c for c in engine.run_until_complete() if c.request_id in ids]
    after = engine.stats()
    tokens = {ids[c.request_id]: list(c.tokens) for c in done}
    if len(tokens) != len(requests):
        raise SmokeFailure(f"{tier}: {len(tokens)} of {len(requests)} "
                           "requests completed")
    for rid, toks in tokens.items():
        if len(toks) != new_tokens or not all(
                0 <= t < engine.cfg.vocab for t in toks):
            raise SmokeFailure(f"{tier}/{rid}: bad tokens {toks}")
    return {"tokens": tokens,
            "decode_s": after["decode_s"] - before["decode_s"],
            "prefill_s": after["prefill_s"] - before["prefill_s"],
            "decode_tokens": sum(len(t) - 1 for t in tokens.values())}


def warm_and_serve(engine, tier: str, requests, *, new_tokens: int) -> dict:
    """Compile `tier` with one short warm-up request, then serve the batch;
    prints compile seconds and the smoke decode rate."""
    t0 = time.perf_counter()
    serve(engine, tier, requests[:1], new_tokens=2)
    compile_s = time.perf_counter() - t0
    res = serve(engine, tier, requests, new_tokens=new_tokens)
    rate = res["decode_tokens"] / max(res["decode_s"], 1e-9)
    log(f"tier {tier}: compile + warm-up {compile_s:.2f} s; "
        f"smoke decode {rate:.1f} tok/s over {len(requests)} requests "
        f"(smoke, not a benchmark); prefill {res['prefill_s']:.3f} s")
    return res


def check_same_tokens(label: str, got: dict, want: dict) -> None:
    bad = [rid for rid in want if got.get(rid) != want[rid]]
    if bad:
        rid = bad[0]
        raise SmokeFailure(f"{label}: tokens differ on {len(bad)} of "
                           f"{len(want)} requests, e.g. {rid}: "
                           f"{got.get(rid)} vs {want[rid]}")
    log(f"{label}: tokens identical on all {len(want)} requests")


def check_exact_logits(engine, prompt: list[int]) -> None:
    """The exact tier's prefill logits are finite and vocab-wide."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import api

    fwd = jax.jit(lambda p, t: api.forward(p, {"tokens": t}, engine.cfg)[0])
    logits = np.asarray(fwd(engine.params, jnp.asarray([prompt], jnp.int32))
                        .astype(jnp.float32))
    if logits.shape != (1, len(prompt), engine.cfg.vocab) or \
            not np.isfinite(logits).all():
        raise SmokeFailure(f"exact logits: shape {logits.shape}, finite "
                           f"{bool(np.isfinite(logits).all())}")
    log(f"exact: prefill logits {logits.shape} finite")


def check_lowrank_gemm(spec, cfg, *, m_prefill: int, m_decode: int,
                       seed: int) -> None:
    """One low-rank GEMM at served width (the FFN up projection): fused and
    skinny kernels equal the stacked kernel bit for bit, and agree with
    the XLA reference to float rounding."""
    import jax.numpy as jnp
    import numpy as np

    from repro.approx import gemm as gemm_mod
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    k, n = cfg.d_model, cfg.d_ff
    b = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
    for m, skinny in ((m_prefill, False), (m_decode, True)):
        a = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
        got = np.asarray(ops.approx_qgemm(a, b, spec, skinny=skinny))
        stacked = np.asarray(ops.approx_qgemm(a, b, spec, fused=False))
        ref = np.asarray(gemm_mod.approx_qgemm(a, b, spec))
        kind = "skinny" if skinny else "fused"
        if not np.array_equal(got, stacked):
            diff = int((got != stacked).sum())
            raise SmokeFailure(f"lowrank {kind} != stacked at ({m},{k},{n}):"
                               f" {diff} elements differ")
        if not np.allclose(got, ref, rtol=1e-6, atol=1.0):
            raise SmokeFailure(f"lowrank {kind} vs XLA reference at "
                               f"({m},{k},{n}): max |diff| "
                               f"{float(np.abs(got - ref).max())}")
        log(f"lowrank r{spec.rank} GEMM ({m},{k},{n}): {kind} == stacked "
            "bit for bit; XLA reference agrees")


def run_one_chip(cfg, *, seed: int = 0, n_requests: int = N_REQUESTS,
                 prompt_len: int = PROMPT_LEN,
                 new_tokens: int = NEW_TOKENS) -> dict:
    """The default smoke: three tiers on the default one-chip mesh under
    kernel policy "auto", then the approximate tiers again on engines
    pinned to "xla" and to "pallas" (the same weights), so the Pallas
    kernels serve inside the model whatever "auto" resolved to."""
    from repro.models import api
    from repro.serving import Engine

    cfg = dataclasses.replace(cfg, kernel_policy="auto")
    kw = dict(capacity=n_requests, max_len=prompt_len + new_tokens,
              prefill_buckets=(prompt_len,), seed=seed)
    t0 = time.perf_counter()
    engine = Engine(cfg, tiers=TIERS, **kw)
    log(f"engine {cfg.name} (d_model {cfg.d_model}, {cfg.n_layers} layers, "
        f"vocab {cfg.vocab}, {cfg.dtype}) on mesh "
        f"{dict(engine.mesh.shape)} built in "
        f"{time.perf_counter() - t0:.2f} s")
    requests = make_requests(cfg.vocab, n=n_requests, prompt_len=prompt_len,
                             new_tokens=new_tokens, seed=seed)
    served = {}
    for tier in TIERS:
        log(f"tier {tier} plans (auto): " + json.dumps(
            tier_plans(engine, tier, prefill_m=prompt_len,
                       decode_m=n_requests)))
        served[tier] = warm_and_serve(engine, tier, requests,
                                      new_tokens=new_tokens)["tokens"]
    check_exact_logits(engine, requests[0][1])

    trunc, lowrank = TIERS[1:]
    pinned = {}
    for policy, tiers in (("xla", (trunc,)), ("pallas", (trunc, lowrank))):
        other = Engine(dataclasses.replace(cfg, kernel_policy=policy),
                       params=engine.params, tiers=tiers, **kw)
        pinned[policy] = {}
        for tier in tiers:
            log(f"tier {tier} plans ({policy}): " + json.dumps(
                tier_plans(other, tier, prefill_m=prompt_len,
                           decode_m=n_requests)))
            pinned[policy][tier] = warm_and_serve(
                other, tier, requests, new_tokens=new_tokens)["tokens"]
        del other           # its prepared weights leave the device
        gc.collect()
    check_same_tokens(f"{trunc} auto vs xla", served[trunc],
                      pinned["xla"][trunc])
    check_same_tokens(f"{trunc} pallas vs xla", pinned["pallas"][trunc],
                      pinned["xla"][trunc])
    # the XLA path may combine the low-rank planes in f32 in another
    # order, so a near-tie may flip a greedy token: reported, not checked
    same = sum(pinned["pallas"][lowrank][r] == served[lowrank][r]
               for r in served[lowrank])
    log(f"{lowrank} pallas vs auto: {same} of {n_requests} requests "
        "token-identical (reported, not checked)")
    check_lowrank_gemm(api.make_spec(cfg, mult=lowrank), cfg,
                       m_prefill=prompt_len, m_decode=n_requests, seed=seed)
    return served


def run_tp(cfg, mesh_spec: str, *, seed: int = 0,
           n_requests: int = N_REQUESTS, prompt_len: int = PROMPT_LEN,
           new_tokens: int = NEW_TOKENS) -> dict:
    """Tensor-parallel serving on `mesh_spec` against one chip on the
    TP_TIERS: trunc2x2 tokens identical, exact identical up to bf16
    near-ties."""
    import jax

    from repro import compat
    from repro.launch.mesh import make_mesh_from_spec
    from repro.models import api
    from repro.serving import Engine

    cfg = dataclasses.replace(cfg, kernel_policy="auto")
    kw = dict(capacity=n_requests, max_len=prompt_len + new_tokens,
              prefill_buckets=(prompt_len,), seed=seed, tiers=TP_TIERS)
    params = api.init_params(cfg, jax.random.key(seed))
    one = Engine(cfg, params=params, mesh=compat.make_mesh(
        (1, 1), ("data", "model"), devices=jax.devices()[:1]), **kw)
    tp = Engine(cfg, params=params, mesh=make_mesh_from_spec(mesh_spec), **kw)
    log(f"TP engine on mesh {dict(tp.mesh.shape)} vs one-chip engine on "
        f"{jax.devices()[0]}")
    requests = make_requests(cfg.vocab, n=n_requests, prompt_len=prompt_len,
                             new_tokens=new_tokens, seed=seed)
    out = {}
    for tier in TP_TIERS:
        want = warm_and_serve(one, tier, requests, new_tokens=new_tokens)
        got = warm_and_serve(tp, tier, requests, new_tokens=new_tokens)
        label = f"{tier} TP {mesh_spec} vs one chip"
        if tier == "exact":
            check_tp_near_ties(label, one, tp, requests, got["tokens"],
                               want["tokens"])
        else:
            check_same_tokens(label, got["tokens"], want["tokens"])
        out[tier] = got["tokens"]
    return out


#: bf16 TP logits may differ from one chip's by this share of the logit
#: scale: row-parallel partial sums reduce in another order, so a hidden
#: element can round to the neighbouring bf16 value (relative 2^-8) and
#: the difference propagates through the layers.  A wrong shard or a
#: missing reduction gives an error of the order of the logits themselves.
TP_LOGIT_RTOL = 2.0 ** -4


def check_tp_near_ties(label: str, one, tp, requests, got: dict,
                       want: dict) -> None:
    """bf16 exact tier under TP: tokens identical, or each request's first
    divergence is a near-tie — the one-chip logit gap between the two
    tokens is within twice the TP-vs-one-chip logit error at that
    position, and that error is within TP_LOGIT_RTOL of the logit scale.
    Both logit rows come from a teacher-forced forward of the same
    context on each engine."""
    import numpy as np

    diverged = [r for r in want if got[r] != want[r]]
    if not diverged:
        log(f"{label}: tokens identical on all {len(want)} requests")
        return
    prompts = dict(requests)
    ctx_len = len(requests[0][1]) + len(want[requests[0][0]])
    last = {e: _last_logits_fn(e, ctx_len) for e in (one, tp)}
    for rid in diverged:
        p = next(i for i, (a, b) in enumerate(zip(want[rid], got[rid]))
                 if a != b)
        context = prompts[rid] + want[rid][:p]
        l1, lt = (last[e](context) for e in (one, tp))
        a, b = want[rid][p], got[rid][p]
        gap = float(l1[a] - l1[b])
        err = float(np.abs(l1 - lt).max())
        scale = float(np.abs(l1).max())
        log(f"{label}: {rid} diverges at token {p}: one chip {a} vs TP {b};"
            f" one-chip gap {gap:.6g}, max |logit diff| {err:.6g}, "
            f"logit scale {scale:.6g}")
        if err > TP_LOGIT_RTOL * scale or abs(gap) > 2 * err:
            raise SmokeFailure(f"{label}: {rid} diverges at token {p} "
                               "beyond a near-tie")
    log(f"{label}: tokens identical on {len(want) - len(diverged)} of "
        f"{len(want)} requests; every divergence is a bf16 near-tie "
        "(reported above)")


def _last_logits_fn(engine, ctx_len: int):
    """context tokens -> float32 logits at the context's last position,
    from a forward over the engine's serving weights on its mesh (right
    padding to a fixed length: causal, so it changes nothing before)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import api
    from repro.sharding import ctx, rules

    mesh, cfg = engine.mesh, engine.cfg

    @jax.jit
    def fwd(params, tokens):
        with ctx.use_rules(mesh, rules.logical_rules(mesh)):
            return api.forward(params, {"tokens": tokens}, cfg)[0]

    def last(context: list[int]) -> np.ndarray:
        tokens = np.zeros((1, ctx_len), np.int32)
        tokens[0, :len(context)] = context
        logits = fwd(engine.exec_params, jnp.asarray(tokens))
        return np.asarray(logits[0, len(context) - 1].astype(jnp.float32))

    return last


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default="",
                    help="run only the tensor-parallel check on this mesh, "
                         "e.g. 'model=4'")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro import configs
    from repro.launch import compile_cache

    device = require_tpu()
    cache_dir = compile_cache.enable()
    log(f"device: {device['kind']} x{device['count']} "
        f"({device['platform']})")
    cache = count_cache_events()
    cfg = configs.get_config(ARCH)
    if args.mesh:
        run_tp(cfg, args.mesh, seed=args.seed)
    else:
        run_one_chip(cfg, seed=args.seed)
    n_files = sum(1 for p in pathlib.Path(cache_dir).rglob("*")
                  if p.is_file())
    log(f"compile cache {cache_dir}: {cache['hits']} hits, "
        f"{cache['misses']} misses, {n_files} files")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        sys.exit(1)
