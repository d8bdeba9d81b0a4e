#!/usr/bin/env python3
"""Compile a cell's programs at its real sizes for a described TPU v5e,
with no chip attached, and print what each needs of the device memory.

    JAX_PLATFORMS=cpu python3 benchmarks/onchip/rehearse.py <workload>...

For each workload: the engine's decode step at the cell's capacity and
`max_len`, the prefill of every bucket, and the arena insert, built by
the engine's own code on shapes only (nothing is allocated).  The compiler
refuses here what it would refuse on the chip: a program that does not
fit, a kernel that does not lower.  Prints one JSON line per workload
with `memory_analysis()` per program and the bytes the engine keeps
(weights and arena) for choosing the capacity.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _nbytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes")}


def rehearse(workload: str, device) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from onchip_bench import spec
    from repro import compat
    from repro.configs import get_config
    from repro.models import api
    from repro.serving.arena import SlotArena, _slot_axis
    from repro.serving.engine import Engine
    from repro.sharding import rules
    from repro.train import train_step as ts

    bench = spec.benchmark()
    wl = spec.workload(bench, workload)
    conf = spec.config(bench, wl)
    cell = spec.cell(workload)
    ref = spec.reference(conf)
    cfg = get_config(conf["model"], **conf["config"])
    cap, max_len = cell["capacity"], cell["max_len"]
    mesh = compat.make_mesh((1, 1), ("data", "model"), devices=[device])
    spec_ = api.make_spec(cfg)

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    p_shape = jax.eval_shape(lambda: ref.make_weights(conf["config"], 1))
    p_shape = jax.eval_shape(
        lambda p: api.prepare_params(p, cfg, spec_), p_shape)
    params = placed(p_shape, rules.param_shardings(p_shape, mesh,
                                                   fsdp=False))
    # the engine's own decode and state shardings, on shapes only
    eng = object.__new__(Engine)
    eng.cfg, eng.mesh, eng.capacity, eng.max_len = cfg, mesh, cap, max_len
    cache = jax.eval_shape(lambda: api.init_cache(cfg, cap, max_len))
    cache["length"] = jax.ShapeDtypeStruct((cap,), jnp.int32)
    eng._state = {
        "cache": cache,
        "tok": jax.ShapeDtypeStruct((cap, 1), jnp.int32),
        "temp": jax.ShapeDtypeStruct((cap,), jnp.float32),
        "topk": jax.ShapeDtypeStruct((cap,), jnp.int32),
        "rng": jax.eval_shape(lambda: jax.random.split(jax.random.key(0),
                                                       cap))}
    eng._state_sh = Engine._state_shardings(eng)
    state = placed(eng._state, eng._state_sh)
    out = {"workload": workload, "weights_bytes": _nbytes(p_shape),
           "arena_bytes": _nbytes(eng._state), "programs": {}}
    dec = Engine._make_decode(eng, spec_).lower(params, state).compile()
    out["programs"]["decode"] = _mem(dec)

    one = NamedSharding(mesh, PartitionSpec())
    prefill = ts.make_prefill_step(cfg, mesh, max_len=max_len, spec=spec_)
    req_cache = None
    for b in cell["prefill_buckets"]:
        tok = jax.ShapeDtypeStruct((1, b), jnp.int32, sharding=one)
        tl = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)
        low = prefill.lower(params, tok, {}, true_len=tl)
        out["programs"][f"prefill_{b}"] = _mem(low.compile())
        req_cache = jax.eval_shape(
            lambda p, t, n: prefill(p, t, {}, true_len=n)[1], params, tok, tl)

    arena = object.__new__(SlotArena)
    flat_r, _ = jax.tree_util.tree_flatten(req_cache)
    flat_a, arena._treedef = jax.tree_util.tree_flatten(cache)
    arena._axes = tuple(_slot_axis(r.shape, a.shape)
                        for r, a in zip(flat_r, flat_a))
    ins = jax.jit(arena._insert_impl).lower(
        state["cache"], placed(req_cache, jax.tree_util.tree_map(
            lambda _: one, req_cache)),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile()
    out["programs"]["insert"] = _mem(ins)
    return out


def main(argv=None) -> int:
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for wl in (argv if argv is not None else sys.argv[1:]):
        print(json.dumps(rehearse(wl, topo.devices[0])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
