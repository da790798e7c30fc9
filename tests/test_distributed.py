"""Multi-device behaviour (subprocess with forced host devices): sharding
policy on the production mesh, small-mesh lowering of train/prefill/decode,
pipeline parallelism, elastic checkpoint restore across mesh sizes."""
import pytest

from conftest import run_subprocess_jax


def test_sharding_policy_divisibility_production():
    """Every param PartitionSpec must divide its dim on the (16,16) and
    (2,16,16) production meshes, for all 10 assigned archs."""
    out = run_subprocess_jax("""
import jax
from repro.configs.registry import ARCHS, get_arch
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import abstract_params
from repro.models.factory import build_model
from repro.sharding.policy import param_pspecs

for multi in (False, True):
    mesh = make_production_mesh(multi_pod=multi)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for name in ARCHS:
        cfg = get_arch(name).replace(head_pad_to=16)
        shapes = abstract_params(build_model(cfg))
        specs = param_pspecs(shapes, mesh)
        for sh, sp in zip(jax.tree.leaves(shapes),
                          jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, '_normalized_spec') or x.__class__.__name__=='PartitionSpec')):
            for dim, part in zip(sh.shape, tuple(sp)):
                if part is None: continue
                axes = (part,) if isinstance(part, str) else part
                prod = 1
                for a in axes: prod *= sizes[a]
                assert dim % prod == 0, (name, sh.shape, tuple(sp))
print("OK")
""", devices=512, timeout=900)
    assert "OK" in out


def test_small_mesh_lower_compile_all_kinds():
    """steps builders lower+compile on a 2x2 host mesh for one dense, one
    MoE and one SSM smoke arch, for train/prefill/decode."""
    out = run_subprocess_jax("""
import jax, dataclasses
from repro.configs.base import ShapeConfig
from repro.configs.registry import smoke_config
from repro.launch import steps
from repro.models.factory import build_model
from repro.train.optimizer import adamw

mesh = jax.make_mesh((2,2), ("data","model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
for arch in ("deepseek-7b", "phi3.5-moe-42b-a6.6b", "mamba2-130m"):
    cfg = smoke_config(arch).replace(head_pad_to=2)
    model = build_model(cfg)
    p_sds, _ = steps.params_sds(model, mesh)
    for kind, name in (("train","t"), ("prefill","p"), ("decode","d")):
        shape = ShapeConfig(name=name, kind=kind, seq_len=32,
                            global_batch=4)
        batch = steps.input_specs(cfg, shape, mesh)
        with mesh:
            if kind == "train":
                opt = adamw(1e-3)
                fn, _ = steps.make_train_step(model, mesh, shape, opt)
                o_sds, _ = steps.opt_state_sds(opt,
                                               steps.abstract_params(model),
                                               mesh)
                jax.jit(fn).lower(p_sds, o_sds, batch).compile()
            elif kind == "prefill":
                fn = steps.make_prefill_step(model, mesh, shape)
                jax.jit(fn).lower(p_sds, batch).compile()
            else:
                fn = steps.make_decode_step(model, mesh, shape)
                c_sds = steps.cache_specs_sds(model, shape, mesh)
                jax.jit(fn).lower(p_sds, c_sds, batch).compile()
    print(arch, "ok")
print("OK")
""", devices=4, timeout=900)
    assert "OK" in out


def test_pipeline_parallel_exact():
    out = run_subprocess_jax("""
import jax, jax.numpy as jnp, numpy as np
from repro.train.pipeline_parallel import pipeline_forward
mesh = jax.make_mesh((2,2), ("pod","data"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
W = jnp.asarray(rng.standard_normal((2, 16, 16)).astype(np.float32)*0.3)
stage_fn = lambda w, h: jnp.tanh(h @ w)
x = jnp.asarray(rng.standard_normal((4, 8, 16)).astype(np.float32))
with mesh:
    out = pipeline_forward(stage_fn, W, x, mesh=mesh)
ref = jnp.stack([stage_fn(W[1], stage_fn(W[0], x[i])) for i in range(4)])
assert float(jnp.max(jnp.abs(out - ref))) < 1e-6
print("OK")
""", devices=4)
    assert "OK" in out


def test_elastic_checkpoint_across_meshes():
    out = run_subprocess_jax("""
import tempfile, jax, jax.numpy as jnp, numpy as np
from repro.train import checkpoint as ck
tree = {"wq": jnp.arange(128, dtype=jnp.bfloat16).reshape(16, 8),
        "scale": jnp.ones(5)}
mesh8 = jax.make_mesh((4, 2), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
mesh2 = jax.make_mesh((2,), ("model",),
                      axis_types=(jax.sharding.AxisType.Auto,))
d = tempfile.mkdtemp()
ck.save(d, 1, tree, mesh=mesh8)
like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
back = ck.restore(d, 1, like, mesh=mesh2)
np.testing.assert_array_equal(np.asarray(back["wq"], np.float32),
                              np.asarray(tree["wq"], np.float32))
assert "model" in str(back["wq"].sharding.spec)
print("OK")
""", devices=8)
    assert "OK" in out


def test_decode_cache_specs_divisible():
    """Cache PartitionSpecs divide on the production mesh for decode_32k
    and long_500k across families (incl. whisper's 1500-frame cross KV)."""
    out = run_subprocess_jax("""
import jax
from repro.configs.registry import ARCHS, get_arch
from repro.configs.shapes import SHAPES, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import cache_specs_sds
from repro.models.factory import build_model

mesh = make_production_mesh()
sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
for name in ARCHS:
    for shape_name in ("decode_32k", "long_500k"):
        cfg = get_arch(name).replace(head_pad_to=16)
        shape = SHAPES[shape_name]
        if not shape_applicable(cfg, shape)[0]:
            continue
        sds = cache_specs_sds(build_model(cfg), shape, mesh)
        for leaf in jax.tree.leaves(sds):
            spec = leaf.sharding.spec
            for dim, part in zip(leaf.shape, tuple(spec)):
                if part is None: continue
                axes = (part,) if isinstance(part, str) else part
                prod = 1
                for a in axes: prod *= sizes[a]
                assert dim % prod == 0, (name, shape_name, leaf.shape,
                                         tuple(spec))
print("OK")
""", devices=512, timeout=900)
    assert "OK" in out
