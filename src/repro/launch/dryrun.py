import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import (jax locks the device count on first
# init).  This module is the ONLY place the 512-placeholder-device world is
# created; tests and benchmarks see the real single CPU device.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes, prove the sharding config is coherent, and
capture memory/cost/collective analyses for EXPERIMENTS.md §Dry-run and
§Roofline.

  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
from jax.sharding import PartitionSpec as P

from repro import compat, configs
from repro.configs.base import ModelConfig, ShapeSpec
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.roofline import analysis as roofline
from repro.sharding import rules
from repro.train import train_step as ts

#: The chip the production meshes stand for: 256/512 placeholder devices
#: are modelled as TPU v5e chips (its published peaks price the terms).
TARGET_DEVICE_KIND = roofline.V5E_KIND


def _sds_with_sharding(tree, shardings):
    return jax.tree_util.tree_map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, shardings)


def _auto_accum(cfg: ModelConfig, shape: ShapeSpec, dp: int) -> int:
    """Gradient-accumulation steps: keep per-device saved layer carries
    (n_layers * mb_local * seq * d_model * 2B) under ~6 GiB."""
    forced = os.environ.get("REPRO_FORCE_ACCUM")
    if forced:
        return int(forced)
    budget = 6 * 1024 ** 3
    accum = 1
    while accum < shape.global_batch:
        mb_local = shape.global_batch // dp // accum
        if mb_local == 0:
            break
        carries = cfg.n_layers * mb_local * shape.seq_len * \
            cfg.d_model * 2
        if carries <= budget or mb_local == 1:
            break
        accum *= 2
    return accum


def _step_options(cfg: ModelConfig, shape: ShapeSpec, mesh) -> ts.StepOptions:
    big = cfg.param_count() >= 100e9
    dp = mesh.devices.size // mesh.shape.get("model", 1)
    return ts.StepOptions(
        accum_steps=_auto_accum(cfg, shape, dp),
        moment_dtype="int8" if big else "f32",
        optimizer="adamw",
    )


def lower_cell(cfg: ModelConfig, shape: ShapeSpec, mesh,
               int8_weights: bool = False) -> tuple:
    """Build + lower the right step function for a cell.  Returns
    (lowered, chips)."""
    chips = mesh.devices.size
    in_specs = configs.input_specs(cfg, shape)
    batch_sh = rules.batch_shardings(in_specs, mesh)
    batch_sds = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                         sharding=batch_sh[k])
                 for k, v in in_specs.items()}

    if shape.kind == "train":
        options = _step_options(cfg, shape, mesh)
        init_fn, _ = ts.make_train_fns(cfg, options)
        st_sh = ts.state_shardings(cfg, options, mesh, init_fn)
        state_sds = _sds_with_sharding(
            jax.eval_shape(init_fn, jax.random.key(0)), st_sh)
        _, step, _ = ts.make_train_step(cfg, options, mesh)
        lowered = step.lower(state_sds, batch_sds)
        return lowered, chips

    # serving cells
    fsdp = rules.should_fsdp(cfg)
    if int8_weights:
        from repro.approx import quant as quant_mod

        def mk_params():
            return quant_mod.quantize_param_tree(
                api.init_params(cfg, jax.random.key(0)))
    else:
        def mk_params():
            return api.init_params(cfg, jax.random.key(0))
    params_shape = jax.eval_shape(mk_params)
    params_sh = rules.param_shardings(params_shape, mesh, fsdp)
    params_sds = _sds_with_sharding(params_shape, params_sh)

    if shape.kind == "prefill":
        extras_sds = {}
        if cfg.family == "encdec":
            extras_sds["frames"] = batch_sds.pop("frames")
        if cfg.cross_every:
            extras_sds["img_embeds"] = batch_sds.pop("img")
        step = ts.make_prefill_step(cfg, mesh)
        lowered = step.lower(params_sds, batch_sds["tokens"], extras_sds)
        return lowered, chips

    # decode: cache as sharded SDS, donated
    cache_shape = configs.cache_specs(cfg, shape)
    cache_sh = rules.cache_shardings(cache_shape, mesh)
    cache_sds = _sds_with_sharding(cache_shape, cache_sh)
    step = ts.make_decode_step(cfg, mesh)
    lowered = step.lower(params_sds, cache_sds, batch_sds["tokens"], {})
    return lowered, chips


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    ok: bool
    skip_reason: str = ""
    error: str = ""
    compile_s: float = 0.0
    memory: dict = dataclasses.field(default_factory=dict)
    roofline: dict = dataclasses.field(default_factory=dict)


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             overrides: dict | None = None, verbose: bool = True,
             int8_weights: bool = False) -> CellResult:
    cfg = configs.get_config(arch, **(overrides or {}))
    shape = configs.SHAPES[shape_name]
    ok, why = configs.cell_supported(cfg, shape)
    if not ok:
        return CellResult(arch, shape_name, mesh_name, ok=False,
                          skip_reason=why)
    t0 = time.time()
    try:
        lowered, chips = lower_cell(cfg, shape, mesh,
                                    int8_weights=int8_weights)
        compiled = lowered.compile()
        dt = time.time() - t0
        mem = roofline.memory_summary(compiled)
        mesh_shape = dict(mesh.shape)
        accum = (_auto_accum(cfg, shape,
                             chips // mesh_shape.get("model", 1))
                 if shape.kind == "train" else 1)
        big = cfg.param_count() >= 100e9
        mem["tpu_estimate"] = roofline.analytic_memory_per_device(
            cfg, shape, mesh_shape, accum=accum,
            moment_bytes=2.2 if big else 8.0)
        mem["accum_steps"] = accum
        terms = roofline.terms_from_compiled(
            compiled, cfg, shape, chips,
            roofline.chip_peaks(TARGET_DEVICE_KIND))
        res = CellResult(arch, shape_name, mesh_name, ok=True,
                         compile_s=dt, memory=mem,
                         roofline=terms.as_dict())
        if verbose:
            r = res.roofline
            print(f"[dryrun] {arch:28s} {shape_name:12s} {mesh_name:6s} "
                  f"OK {dt:6.1f}s  flops={r['flops']:.3e} "
                  f"bytes={r['hbm_bytes']:.3e} "
                  f"coll={r['collective_bytes']:.3e} "
                  f"bottleneck={r['bottleneck']}")
        return res
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        if verbose:
            print(f"[dryrun] {arch:28s} {shape_name:12s} {mesh_name:6s} "
                  f"FAIL: {type(e).__name__}: {e}")
            traceback.print_exc()
        return CellResult(arch, shape_name, mesh_name, ok=False,
                          error=f"{type(e).__name__}: {e}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true",
                    help="all archs x shapes x both meshes")
    ap.add_argument("--out", default="")
    ap.add_argument("--mult", default="",
                    help="approximate multiplier (paper mode)")
    ap.add_argument("--mesh-override", default="",
                    help="single-pod mesh reshape 'data,model' (256 chips; "
                         "perf-iteration lever, e.g. '32,8' for archs "
                         "whose heads/experts don't divide 16)")
    ap.add_argument("--int8-weights", action="store_true",
                    help="serve decode/prefill with int8-stored weights "
                         "(the paper's accelerators are int8; halves the "
                         "weight HBM traffic of decode cells)")
    args = ap.parse_args()

    if args.all:
        args.arch = args.shape = "all"
        args.mesh = "both"

    archs = configs.ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(configs.SHAPES) if args.shape == "all" else [args.shape]
    meshes = []
    if args.mesh_override:
        d, m = (int(x) for x in args.mesh_override.split(","))
        assert d * m == 256, "single-pod override must use 256 chips"
        meshes.append((f"single{d}x{m}",
                       compat.make_mesh((d, m), ("data", "model"))))
    if args.mesh in ("single", "both") and not args.mesh_override:
        meshes.append(("single", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both") and not args.mesh_override:
        meshes.append(("multi", make_production_mesh(multi_pod=True)))

    overrides = {"mult": args.mult} if args.mult else {}
    results = []
    for arch in archs:
        for shape_name in shapes:
            for mesh_name, mesh in meshes:
                with mesh:
                    results.append(run_cell(
                        arch, shape_name, mesh, mesh_name, overrides,
                        int8_weights=args.int8_weights))

    n_ok = sum(r.ok for r in results)
    n_skip = sum(bool(r.skip_reason) for r in results)
    n_fail = len(results) - n_ok - n_skip
    print(f"\n[dryrun] {n_ok} ok / {n_skip} skipped / {n_fail} FAILED "
          f"of {len(results)} cells")
    if args.out:
        with open(args.out, "w") as f:
            json.dump([dataclasses.asdict(r) for r in results], f, indent=1)
        print(f"[dryrun] wrote {args.out}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
