"""shard_map expert-parallel MoE (explicit all_to_all) vs the local path.

Runs in a subprocess with 8 forced host devices so the mesh is real.
"""

import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import api, moe
from repro.sharding.context import activation_axes

from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_config("mixtral-8x22b", smoke=True)   # 4 experts on model=4
params = api.init_params(cfg, jax.random.PRNGKey(0))

# layer-level: exact agreement in f32
lp = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
x = jax.random.normal(jax.random.PRNGKey(2), (4, 64, cfg.d_model),
                      jnp.float32) * 0.3
y_ref, aux_ref = moe.moe_mlp(lp, x, cfg)
with activation_axes(mesh):
    y_sm, aux_sm = jax.jit(lambda p, xx: moe.moe_mlp(p, xx, cfg))(lp, x)
np.testing.assert_allclose(np.asarray(y_sm), np.asarray(y_ref),
                           rtol=1e-5, atol=1e-5)
np.testing.assert_allclose(float(aux_sm["lb_loss"]),
                           float(aux_ref["lb_loss"]), rtol=1e-6)
print("LAYER_OK")

# end-to-end: distributions agree (bf16 reduction-order noise only) and the
# compiled program really carries all-to-all collectives
batch = api.make_dummy_batch(cfg, 4, 64)
ref = api.forward(cfg, params, batch)
with activation_axes(mesh):
    fn = jax.jit(lambda p, b: api.forward(cfg, p, b))
    out = fn(params, batch)
    txt = fn.lower(params, batch).compile().as_text()
pp = jax.nn.softmax(out.astype(jnp.float32), -1)
pr = jax.nn.softmax(ref.astype(jnp.float32), -1)
assert float(jnp.max(jnp.abs(pp - pr))) < 5e-3
assert "all-to-all" in txt
print("E2E_OK", txt.count("all-to-all"))
"""


def test_shardmap_moe_subprocess():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "LAYER_OK" in out.stdout and "E2E_OK" in out.stdout
