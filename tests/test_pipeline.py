"""Pipeline-parallel forward must equal the sequential layer stack."""


def test_pipeline_matches_sequential(multidevice):
    out = multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.pipeline import pipeline_forward

rng = np.random.default_rng(0)
L, D, MB, NM = 8, 16, 2, 6
params = {"w": jnp.asarray(rng.normal(0, 0.3, (L, D, D)), jnp.float32),
          "b": jnp.asarray(rng.normal(0, 0.1, (L, D)), jnp.float32)}
x = jnp.asarray(rng.normal(0, 1, (NM, MB, D)), jnp.float32)

def layer(lp, h):
    return jnp.tanh(h @ lp["w"] + lp["b"])

# sequential reference
def seq(x1):
    def one(h, i):
        return layer(jax.tree.map(lambda a: a[i], params), h), None
    h, _ = jax.lax.scan(one, x1, jnp.arange(L))
    return h
want = jax.vmap(seq)(x)

from repro.launch.mesh import mesh_of
mesh = mesh_of((4,), ("pipe",))
got = pipeline_forward(layer, params, x, mesh)
err = float(jnp.max(jnp.abs(got - want)))
print("err", err)
assert err < 1e-5, err
print("OK")
""", n_devices=4)
    assert "OK" in out
