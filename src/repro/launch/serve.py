"""Batched serving CLI: a thin shell over the continuous-batching Engine
(`repro.serving`).

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --batch 4 --prompt-len 64 --gen 32

Submits a batch of synthetic prompts as requests, serves them through the
engine's prefill-then-join decode loop, and reports per-phase latency and
tokens/s.  `--mult` serves under an approximate multiplier (the paper's
accelerator in simulation) on the exact same code path.  All four model
families go through the engine's single jitted prefill — no family
special cases.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro import configs
from repro.data import synthetic
from repro.launch import compile_cache
from repro.serving import Engine, Request, SamplingParams


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mult", default="")
    ap.add_argument("--kernel-policy", default="",
                    choices=["", "auto", "pallas", "xla"],
                    help="Pallas/XLA GEMM dispatch (kernels/dispatch.py); "
                         "'pallas' on CPU runs kernels in interpret mode")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="",
                    help="device mesh spec, e.g. 'model=4,data=2' "
                         "(default: $REPRO_MESH, then the host mesh); a "
                         "multi-device 'model' axis serves tensor-parallel")
    ap.add_argument("--capacity", type=int, default=0,
                    help="decode-arena slots (default: --batch)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filter (0 = off)")
    args = ap.parse_args(argv)

    cfg = configs.apply_overrides(configs.get_config(args.arch),
                                  reduced=args.reduced, mult=args.mult,
                                  kernel_policy=args.kernel_policy)

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab, (args.batch, args.prompt_len))
    frames = img = None
    if cfg.family == "encdec":
        frames = synthetic.frames_batch(args.batch, cfg.enc_seq,
                                        cfg.d_model, 0, args.seed)
    if cfg.cross_every:
        img = synthetic.img_batch(args.batch, cfg.n_img_tokens,
                                  cfg.d_model, 0, args.seed)

    from repro.launch.mesh import make_mesh_from_spec
    max_len = args.prompt_len + args.gen
    eng = Engine(cfg, capacity=args.capacity or args.batch, max_len=max_len,
                 prefill_buckets=(args.prompt_len,), seed=args.seed,
                 mesh=make_mesh_from_spec(args.mesh))
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                        max_new_tokens=args.gen)
    for i in range(args.batch):
        extras = {}
        if frames is not None:
            extras["frames"] = frames[i]
        if img is not None:
            extras["img_embeds"] = img[i]
        eng.submit(Request(f"r{i}", prompts[i].tolist(), sp,
                           extras=extras or None))
    done = eng.run_until_complete()

    stats = eng.stats()
    decode_toks = sum(len(c.tokens) - 1 for c in done)
    toks_per_s = decode_toks / max(stats["decode_s"], 1e-9)
    first = next(c for c in done if c.request_id == "r0")
    print(f"[serve] arch={cfg.name} mult={cfg.mult or 'exact'} "
          f"batch={args.batch}")
    print(f"[serve] prefill {args.prompt_len} toks: "
          f"{stats['prefill_s']:.3f}s; decode: {toks_per_s:.1f} tok/s")
    print(f"[serve] sample continuation ids: "
          f"{np.asarray(first.tokens[:16])}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
