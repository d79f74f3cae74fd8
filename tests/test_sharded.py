"""Multi-device correctness (8 fake host devices via a subprocess, since the
main pytest process is pinned to 1 device): sharded-vs-single-device loss
parity, MoE EP paths vs the dense oracle, elastic checkpoint resharding."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, tempfile, dataclasses
sys.path.insert(0, %(src)r)
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs import get_config, scaled_down
import repro.configs.base as CB
from repro.models import model as M
from repro.models.sharding import Rules
from repro.launch import mesh as MX
from repro.ckpt import checkpoint as CK

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
key = jax.random.PRNGKey(0)
B, S = 8, 32

# ---- 1) sharded loss == single-device loss (dense + moe ep + ep_a2a) ----
for arch, impls in [("llama3.2-1b", ["dense"]),
                    ("moonshot-v1-16b-a3b", ["ep", "ep_a2a"])]:
    cfg = scaled_down(get_config(arch), d_model=64, d_ff=128, vocab=1024,
                      n_heads=4, n_kv_heads=2, head_dim=16)
    if cfg.moe:
        cfg = dataclasses.replace(cfg, moe=CB.MoESpec(8, 2, 64))
    params = M.init_params(cfg, key, jnp.float32, max_seq=64)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    labels = jnp.roll(tokens, -1, 1)
    ref_loss, _ = M.lm_loss(cfg, params, tokens, labels, M.Ctx())
    for impl in impls:
        rules = Rules()
        ctx = M.Ctx(rules=rules, mesh=mesh, moe_impl=impl)
        pshard = MX.tree_shardings(mesh, rules,
                                   jax.eval_shape(lambda: params),
                                   M.param_axes(cfg))
        tshard = NamedSharding(mesh, P(("pod", "data"), None))
        with jax.set_mesh(mesh):
            loss, _ = jax.jit(
                lambda p, t, y: M.lm_loss(cfg, p, t, y, ctx),
                in_shardings=(pshard, tshard, tshard))(params, tokens,
                                                       labels)
        d = abs(float(loss) - float(ref_loss))
        tol = 6e-3 if impl != "dense" else 1e-5   # EP drops over capacity
        assert d < tol, (arch, impl, d)
        print(f"PARITY {arch} {impl} d={d:.2e}")

# ---- 2) elastic checkpoint: save on mesh A, restore on mesh B -----------
cfg = scaled_down(get_config("smollm-360m"), n_units=2)
params = M.init_params(cfg, key, jnp.float32, max_seq=64)
axes = M.param_axes(cfg)
with tempfile.TemporaryDirectory() as d:
    CK.save(d, params, step=1)
    mesh_b = jax.make_mesh((4, 2), ("data", "model"),
                           axis_types=(AxisType.Auto,) * 2)
    shardings = MX.tree_shardings(mesh_b, Rules(),
                                  jax.eval_shape(lambda: params), axes)
    flat_names = []
    for kp, _ in jax.tree_util.tree_flatten_with_path(params)[0]:
        flat_names.append(jax.tree_util.keystr(kp))
    flat_sh = jax.tree.leaves(shardings,
                              is_leaf=lambda x: hasattr(x, "spec"))
    table = dict(zip(flat_names, flat_sh))
    restored = CK.restore(d, params, sharding_fn=lambda n: table[n])
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(params)):
        assert jnp.allclose(a, b)
    any_sharded = any(
        len(x.sharding.device_set) > 1 for x in jax.tree.leaves(restored))
    assert any_sharded, "restore did not place on the new mesh"
    print("ELASTIC OK")
print("ALL_OK")
"""


@pytest.mark.slow
def test_sharded_parity_and_elastic_restore():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT % {"src": os.path.abspath(src)}],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ALL_OK" in out.stdout
    assert out.stdout.count("PARITY") == 3
    assert "ELASTIC OK" in out.stdout
