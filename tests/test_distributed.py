"""Distributed runtime: sharding rules, compressed collectives, pipeline
parallelism, sharded train step.  Multi-device cases run in a subprocess
with XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main test
process keeps its single real device (per the brief)."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(code: str, n: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_param_pspec_rules():
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh
    from repro.sharding import rules
    mesh = make_mesh((1, 1), ("data", "model"))

    class K:  # fake DictKey
        def __init__(self, k):
            self.key = k

    # column parallel
    assert rules.param_pspec((K("layers"), K("wq")), (22, 2048, 2048),
                             mesh, fsdp=True) == P(None, "data", "model")
    # row parallel
    assert rules.param_pspec((K("layers"), K("wo")), (22, 2048, 2048),
                             mesh, fsdp=False) == P(None, "model", None)
    # norms replicated
    assert rules.param_pspec((K("layers"), K("ln1")), (22, 2048),
                             mesh) == P()
    # embedding vocab-sharded
    assert rules.param_pspec((K("embed"),), (32000, 2048), mesh) == \
        P("model", None)


def test_param_pspec_divisibility_drop():
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_abstract_mesh
    from repro.sharding import rules

    class K:
        def __init__(self, k):
            self.key = k

    mesh16 = make_abstract_mesh((2, 16), ("data", "model"))
    # kv proj with kv*hd=60 not divisible by 16 -> model axis dropped
    assert rules.param_pspec((K("wk"),), (2048, 60), mesh16,
                             fsdp=False) == P(None, None)
    # same name, divisible dim -> sharded
    assert rules.param_pspec((K("wk"),), (2048, 64), mesh16,
                             fsdp=False) == P(None, "model")
    # row-parallel with contraction dim not divisible -> dropped; the
    # fsdp dim still applies when it divides
    assert rules.param_pspec((K("wo"),), (60, 2048), mesh16,
                             fsdp=True) == P(None, "data")
    # stacked leaf: leading layer dims stay None, core rule on the tail
    assert rules.param_pspec((K("layers"), K("wq")), (22, 2048, 2048),
                             mesh16, fsdp=False) == P(None, None, "model")


def test_prepared_weight_leaves_inherit_weight_rules():
    """PreparedWeight wrapper fields (attr keys) resolve to the enclosing
    weight's partition rule; a REAL param named like a wrapper field
    (dict key "wq") still resolves normally."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.approx import gemm as G
    from repro.compat import make_abstract_mesh
    from repro.sharding import rules
    import jax.numpy as jnp

    mesh = make_abstract_mesh((1, 4), ("data", "model"))
    pw = G.prepare_weight(jnp.ones((128, 64), jnp.float32),
                          G.spec_from_name("pareto:0.02:r2"))
    tree = {"layers": {"wq": pw}}
    shapes = jax.tree_util.tree_map_with_path(
        lambda p, l: rules.param_pspec(p, l.shape, mesh, fsdp=False), tree)
    got = shapes["layers"]["wq"]
    # w and wq carry the (k, n) col rule; sw (1, n) shards n; planes
    # (R, k, n) gets a leading None
    assert got.w == P(None, "model")
    assert got.wq == P(None, "model")
    assert got.sw == P(None, "model")
    assert got.planes == P(None, None, "model")


def test_tp_fused_qgemm_shard_map_parity():
    """Fused approx-QGEMM through shard_map on a 4-way model axis vs the
    single-device kernel: bit-identical for the pure-integer trunc mode;
    lowrank matches to the f32 flush's FMA-fusion jitter (the per-plane
    int32 accumulators are exact — only the final scale-and-sum is
    compiled per program context)."""
    run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.approx import gemm as G
        from repro.kernels import ops
        from repro.launch.mesh import make_mesh_from_spec

        mesh = make_mesh_from_spec("model=4,data=2")
        rng = np.random.default_rng(0)
        m, k, n = 96, 160, 256
        a = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
        b = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)

        spec = G.spec_from_name("trunc2x2")
        ref = np.asarray(ops.approx_qgemm(a, b, spec))
        tp = np.asarray(jax.jit(
            lambda a, b: ops.approx_qgemm_tp(a, b, spec, mesh))(a, b))
        assert np.array_equal(ref, tp), "trunc TP != single-device kernel"
        # the stacked reference twin stays bit-identical under TP too
        tps = np.asarray(jax.jit(lambda a, b: ops.approx_qgemm_tp(
            a, b, spec, mesh, fused=False))(a, b))
        assert np.array_equal(ref, tps)

        spec = G.spec_from_name("pareto:0.02:r2")
        ref = np.asarray(ops.approx_qgemm(a, b, spec))
        tp = np.asarray(jax.jit(
            lambda a, b: ops.approx_qgemm_tp(a, b, spec, mesh))(a, b))
        err = np.abs(tp - ref) / np.maximum(np.abs(ref), 1.0)
        assert err.max() < 1e-3, err.max()
        print("OK")
    """)


def test_serving_decode_token_parity_across_meshes():
    """Greedy decode through the Engine on a 1-die mesh must be
    token-identical to a 4-way model-parallel mesh, for an attention
    family and an SSM family (the tentpole acceptance criterion)."""
    run_devices("""
        import jax, numpy as np
        from repro import configs
        from repro.models import api
        from repro.serving import Engine, Request, SamplingParams
        from repro.launch.mesh import make_mesh_from_spec

        def serve(arch, mesh_spec):
            cfg = configs.reduced(configs.get_config(arch))
            params = api.init_params(cfg, jax.random.key(0))
            eng = Engine(cfg, params, capacity=3, max_len=64, seed=0,
                         mesh=make_mesh_from_spec(mesh_spec))
            rng = np.random.default_rng(5)
            for i, n in enumerate([5, 19, 33]):
                eng.submit(Request(f"r{i}",
                                   rng.integers(1, 256, (n,)).tolist(),
                                   SamplingParams(max_new_tokens=6)))
            done = {c.request_id: c.tokens
                    for c in eng.run_until_complete()}
            return done, eng.stats()

        for arch in ("tinyllama-1.1b", "mamba2-370m"):
            one, _ = serve(arch, "data=1,model=1")
            tp, stats = serve(arch, "model=4,data=2")
            assert one == tp, (arch, one, tp)
            assert stats["mesh"] == {"data": 2, "model": 4}, stats
            assert stats["evictions"]["length"] == 3, stats
        print("OK")
    """, timeout=1800)


def test_tp_serving_calibration_anchor():
    """The delay anchor can measure TENSOR-PARALLEL serving decode, with
    the analytical mirror running the same die partitioning."""
    run_devices("""
        from repro.core import calibrate as cal
        c = cal.calibrate_serving(requests=2, capacity=2, max_len=32,
                                  prompt=6, gen=3,
                                  mesh_spec="model=2,data=1")
        assert c.source == "serving"
        assert c.meta["n_dies"] == 2, c.meta
        assert c.measured > 0 and c.analytical > 0 and c.scale > 0
        assert "x 2 dies" in c.anchor, c.anchor
        print("OK")
    """, timeout=1200)


def test_engine_respects_repro_mesh_env(monkeypatch):
    """REPRO_MESH reaches the engine through make_mesh_from_spec."""
    import jax
    from repro.launch import mesh as meshmod
    monkeypatch.setenv("REPRO_MESH", "data=1,model=1")
    m = meshmod.make_mesh_from_spec()
    assert dict(m.shape) == {"data": 1, "model": 1}
    monkeypatch.setenv("REPRO_MESH", "model=999")
    import pytest
    with pytest.raises(ValueError, match="devices"):
        meshmod.make_mesh_from_spec()
    # explicit spec takes precedence over the env
    m2 = meshmod.make_mesh_from_spec("model=1,data=1")
    assert dict(m2.shape) == {"data": 1, "model": 1}


def test_moe_expert_sharding_fallback():
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_abstract_mesh
    from repro.sharding import rules

    class K:
        def __init__(self, k):
            self.key = k

    mesh = make_abstract_mesh((1, 2), ("data", "model"))
    # 128 experts % 2 == 0 -> EP on experts dim
    assert rules.param_pspec((K("we_gate"),), (128, 512, 256), mesh) == \
        P("model", "data", None)
    # 3 experts % 2 != 0 -> TP inside the expert instead
    assert rules.param_pspec((K("we_gate"),), (3, 512, 256), mesh) == \
        P(None, "data", "model")
    # production mesh: grok's 8 experts vs model=16 -> in-expert TP
    mesh16 = make_abstract_mesh((16, 16), ("data", "model"))
    assert rules.param_pspec((K("we_gate"),), (8, 6144, 32768), mesh16) == \
        P(None, "data", "model")
    # llama4's 128 experts vs model=16 -> EP
    assert rules.param_pspec((K("we_gate"),), (128, 5120, 8192),
                             mesh16) == P("model", "data", None)


def test_compressed_allreduce_matches_psum():
    run_devices("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.compat import make_mesh
        from repro.sharding import compress

        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        xs = jnp.asarray(rng.standard_normal((8, 4096)), jnp.float32)

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"), check_vma=False)
        def f(x):
            local = x[0]
            s = compress.compressed_allreduce(local, "data")
            return s[None]

        got = np.asarray(f(xs))
        want = np.asarray(xs.sum(0))
        # int8 wire: error bounded by ~n_hops quantization steps of the
        # tensor scale (NOT element-relative — near-zero sums would make
        # any quantized scheme look unbounded)
        tol = 0.05 * np.abs(want).max()
        for i in range(8):
            assert np.abs(got[i] - want).max() < tol
            np.testing.assert_allclose(got[i], got[0], rtol=0, atol=0)
        print("OK")
    """)


def test_error_feedback_reduces_bias():
    run_devices("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import PartitionSpec as P
        from repro.compat import make_mesh
        from repro.sharding import compress

        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(1)
        g = jnp.asarray(rng.standard_normal((8, 1024)), jnp.float32)

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data")), check_vma=False)
        def step(gs, es):
            out, e2 = compress.ef_compressed_allreduce(gs[0], es[0], "data")
            return out[None], e2[None]

        # accumulate the same gradient over steps; with EF the running sum of
        # compressed reductions tracks the true sum closely
        e = jnp.zeros_like(g)
        acc = np.zeros(1024)
        for _ in range(8):
            out, e = step(g, e)
            acc += np.asarray(out[0])
        want = 8 * np.asarray(g.sum(0))
        rel = np.abs(acc - want).mean() / (np.abs(want).mean() + 1e-6)
        assert rel < 0.02, rel
        print("OK")
    """)


def test_pipeline_matches_sequential():
    run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh
        from repro.sharding import pipeline

        mesh = make_mesh((4,), ("stage",))
        rng = np.random.default_rng(2)
        S, M, MB, D = 4, 6, 8, 32
        w = jnp.asarray(rng.standard_normal((S, D, D)) * 0.3, jnp.float32)

        def stage_fn(wi, x):
            return jnp.tanh(x @ wi)

        x = jnp.asarray(rng.standard_normal((M, MB, D)), jnp.float32)
        got = pipeline.pipeline_apply(stage_fn, w, x, mesh, "stage")
        want = x
        for i in range(S):
            want = jnp.tanh(want @ w[i])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        print("OK", pipeline.bubble_fraction(S, M))
    """)


def test_sharded_train_step_runs_and_matches_single_device():
    run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.compat import make_mesh
        from repro.configs.base import reduced
        from repro.models import api
        from repro.train import train_step as ts
        from repro.data import synthetic

        cfg = reduced(configs.get_config("tinyllama-1.1b"), remat=True)
        options = ts.StepOptions(accum_steps=2, lr=1e-3, total_steps=50)
        mesh = make_mesh((4, 2), ("data", "model"))
        init_fn, step, st_sh = ts.make_train_step(cfg, options, mesh,
                                                  donate=False)
        state = jax.device_put(init_fn(jax.random.key(0)), st_sh)
        batch_np = synthetic.lm_batch(cfg.vocab, 8, 64, step=0)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        state2, m1 = step(state, batch)
        state3, m2 = step(state2, batch)
        assert np.isfinite(float(m1["loss"])) and np.isfinite(float(m2["loss"]))
        assert float(m2["loss"]) < float(m1["loss"]) + 1.0

        # single-device reference: same init, same batch, same update
        mesh1 = make_mesh((1, 1), ("data", "model"))
        init1, step1, sh1 = ts.make_train_step(cfg, options, mesh1,
                                               donate=False)
        s1 = jax.device_put(init1(jax.random.key(0)), sh1)
        s1b, r1 = step1(s1, batch)
        np.testing.assert_allclose(float(r1["loss"]), float(m1["loss"]),
                                   rtol=2e-4)
        print("OK", float(m1["loss"]))
    """)


def test_elastic_checkpoint_restore_across_meshes():
    run_devices("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.compat import make_mesh
        from repro.configs.base import reduced
        from repro.train import train_step as ts, checkpoint as ckpt
        from repro.data import synthetic

        cfg = reduced(configs.get_config("tinyllama-1.1b"))
        options = ts.StepOptions(lr=1e-3, total_steps=50)
        d = tempfile.mkdtemp()
        mgr = ckpt.CheckpointManager(d)

        mesh_a = make_mesh((2, 4), ("data", "model"))
        init_fn, step_a, sh_a = ts.make_train_step(cfg, options, mesh_a,
                                                   donate=False)
        state = jax.device_put(init_fn(jax.random.key(0)), sh_a)
        batch = {k: jnp.asarray(v) for k, v in
                 synthetic.lm_batch(cfg.vocab, 8, 64, step=0).items()}
        state, _ = step_a(state, batch)
        mgr.save(state, step=1)

        # restore onto a DIFFERENT mesh shape (elastic rescale)
        mesh_b = make_mesh((8, 1), ("data", "model"))
        init_b, step_b, sh_b = ts.make_train_step(cfg, options, mesh_b,
                                                  donate=False)
        target = jax.eval_shape(init_b, jax.random.key(0))
        restored, at_step = mgr.restore(target, shardings=sh_b)
        assert at_step == 1
        # values identical regardless of mesh
        a = jax.device_get(state["params"]["embed"])
        b = jax.device_get(restored["params"]["embed"])
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and training continues
        restored2, m = step_b(restored, batch)
        assert np.isfinite(float(m["loss"]))
        print("OK")
    """)


def test_hierarchical_batch_sharding_multipod():
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_abstract_mesh
    from repro.sharding import rules
    mesh = make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    spec = rules.batch_pspec("tokens", (256, 4096), mesh)
    assert spec == P(("pod", "data"), None)
    # batch=1 (long_500k) not divisible -> replicated
    spec1 = rules.batch_pspec("tokens", (1, 1), mesh)
    assert spec1 == P(None, None)
