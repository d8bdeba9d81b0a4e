"""The repro.compat shims must resolve on the *installed* JAX, and the
kernel-dispatch policy must behave: interpret=True off-TPU, policy knobs
honored, and the Pallas path reachable from the model layer (not just the
direct kernel tests)."""

import dataclasses
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.kernels import dispatch

SRC = pathlib.Path(__file__).resolve().parent.parent / "src" / "repro"


# --- backend probe -------------------------------------------------------------

def test_backend_probe():
    assert compat.backend() in ("cpu", "gpu", "tpu")
    assert compat.is_tpu_backend() == (compat.backend() == "tpu")


# --- pallas compiler-params shim ----------------------------------------------

def test_tpu_compiler_params_resolves():
    from jax.experimental.pallas import tpu as pltpu
    params = compat.tpu_compiler_params(
        dimension_semantics=("parallel", "arbitrary"))
    assert isinstance(params, pltpu.CompilerParams)
    assert tuple(params.dimension_semantics) == ("parallel", "arbitrary")


def test_dimension_semantics_normalization():
    assert compat.normalize_dimension_semantics(
        ("parallel", "sequential")) == ("parallel", "arbitrary")
    with pytest.raises(ValueError):
        compat.normalize_dimension_semantics(("bogus",))


def test_compiler_params_accepted_by_pallas_call():
    """The shim's output must be accepted end-to-end by pl.pallas_call."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    x = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
    out = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=dispatch.interpret_mode(),
    )(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x) * 2.0)


# --- mesh shims ----------------------------------------------------------------

def test_make_abstract_mesh_on_installed_jax():
    mesh = compat.make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    assert tuple(mesh.axis_names) == ("pod", "data", "model")
    assert dict(mesh.shape) == {"pod": 2, "data": 16, "model": 16}


def test_make_abstract_mesh_feeds_sharding_rules():
    from jax.sharding import PartitionSpec as P
    from repro.sharding import rules
    mesh = compat.make_abstract_mesh((16, 16), ("data", "model"))
    assert rules.batch_pspec("tokens", (256, 4096), mesh) == \
        P(("data",), None)


def test_make_mesh_builds_device_mesh():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert tuple(mesh.axis_names) == ("data", "model")
    assert mesh.devices.shape == (1, 1)
    # explicit-devices path: the head of the list is used
    mesh2 = compat.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    assert mesh2.devices.shape == (1,)
    with pytest.raises(ValueError):
        compat.make_mesh((2,), ("data",), devices=jax.devices()[:1])


@pytest.mark.parametrize("build", ["concrete", "abstract"])
def test_meshes_have_auto_axes(build):
    """Every mesh carries Auto axis types: with JAX's default Explicit
    axes, array types carry their shardings and a gather on a
    vocab-sharded embedding raises ShardingTypeError even on 1x1."""
    from jax.sharding import AxisType
    make = compat.make_mesh if build == "concrete" else \
        compat.make_abstract_mesh
    mesh = make((1, 1), ("data", "model"))
    assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)


def test_embedding_gather_on_sharded_table():
    """The call that crashed serving and training: jnp.take on an
    embedding committed vocab-sharded over the "model" axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    table = jax.device_put(jnp.arange(512 * 8, dtype=jnp.float32)
                           .reshape(512, 8),
                           NamedSharding(mesh, P("model", None)))
    ids = jnp.asarray([[3, 7, 511]], jnp.int32)
    out = jax.jit(lambda t, i: jnp.take(t, i, axis=0))(table, ids)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(table)[np.asarray(ids)])


def test_make_abstract_mesh_rejects_mismatched_axes():
    with pytest.raises(ValueError):
        compat.make_abstract_mesh((1, 2), ("only_one",))


# --- kernel dispatch -----------------------------------------------------------

def test_dispatch_interpret_mode_off_tpu():
    if compat.is_tpu_backend():
        pytest.skip("running on a real TPU")
    assert dispatch.interpret_mode() is True


def test_dispatch_policy_table(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_POLICY", raising=False)
    shapes = dict(m=512, k=512, n=512)
    assert dispatch.use_pallas_gemm("pallas", **shapes) is True
    assert dispatch.use_pallas_gemm("xla", **shapes) is False
    if not compat.is_tpu_backend():
        # auto never picks interpret-mode Pallas for the hot path
        assert dispatch.use_pallas_gemm("auto", **shapes) is False
    with pytest.raises(ValueError):
        dispatch.resolve("mosaic")


def test_dispatch_env_default(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_POLICY", "pallas")
    assert dispatch.default_policy() == "pallas"
    assert dispatch.use_pallas_gemm(None, m=8, k=8, n=8) is True
    monkeypatch.setenv("REPRO_KERNEL_POLICY", "nonsense")
    assert dispatch.default_policy() == "auto"


def test_spec_policy_is_static_pytree_meta(monkeypatch):
    """Policy changes must change the treedef (fresh jit cache key)."""
    monkeypatch.delenv("REPRO_KERNEL_POLICY", raising=False)
    from repro.approx import gemm as G
    spec = G.spec_from_name("trunc2x2")
    sp = spec.with_policy("pallas")
    assert sp.policy == "pallas" and spec.policy == "auto"
    assert sp.with_policy("pallas") is sp
    t1 = jax.tree_util.tree_structure(spec)
    t2 = jax.tree_util.tree_structure(sp)
    assert t1 != t2


def test_model_forward_exercises_pallas_path():
    """A reduced model forward under kernel_policy="pallas" runs every GEMM
    through the interpret-mode Pallas kernel and matches the XLA policy
    bit-for-bit on the integer (trunc) path."""
    from repro import configs
    from repro.configs.base import reduced
    from repro.models import api

    outs = {}
    for policy in ("xla", "pallas"):
        cfg = dataclasses.replace(
            reduced(configs.get_config("tinyllama-1.1b")),
            mult="trunc2x2", kernel_policy=policy)
        spec = api.make_spec(cfg)
        assert spec is not None and spec.policy == policy
        params = api.init_params(cfg, jax.random.key(0))
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (2, 16)),
            jnp.int32)
        logits, _ = api.forward(params, {"tokens": tokens}, cfg, spec)
        outs[policy] = np.asarray(logits, dtype=np.float32)
    np.testing.assert_allclose(outs["pallas"], outs["xla"],
                               rtol=1e-6, atol=1e-6)


def test_attention_policy_dispatch():
    """impl="flash" honors the kernel policy: "pallas" runs the Pallas
    kernel (interpret off-TPU), "xla" the blockwise twin; results agree."""
    from repro.models import common as C
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((1, 64, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 64, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 64, 2, 64)), jnp.float32)
    o_pallas = C.attention(q, k, v, impl="flash", policy="pallas")
    o_xla = C.attention(q, k, v, impl="flash", policy="xla")
    np.testing.assert_allclose(np.asarray(o_pallas), np.asarray(o_xla),
                               rtol=2e-5, atol=2e-5)


# --- drift hygiene -------------------------------------------------------------

def test_no_direct_version_sensitive_api_use_outside_compat():
    """No module outside repro/compat may spell the version-sensitive APIs
    directly (the acceptance rule that keeps future JAX drift localized)."""
    banned = re.compile(r"CompilerParams|AbstractMesh\s*\(")
    offenders = []
    for path in SRC.rglob("*.py"):
        if "compat" in path.parts:
            continue
        if banned.search(path.read_text()):
            offenders.append(str(path.relative_to(SRC)))
    assert not offenders, f"direct version-sensitive JAX use in: {offenders}"
