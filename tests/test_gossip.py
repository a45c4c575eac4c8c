import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gossip, topology


def tree(n, seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (n, 6, 4)),
            "b": jax.random.normal(jax.random.fold_in(k, 1), (n, 3))}


def test_mix_dense_preserves_mean():
    n = 8
    w = jnp.asarray(topology.ring(n).w(), jnp.float32)
    t = tree(n)
    mixed = gossip.mix_dense(w, t)
    for a, b in zip(jax.tree.leaves(gossip.node_mean(t)),
                    jax.tree.leaves(gossip.node_mean(mixed))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_mix_dense_contracts_consensus():
    n = 8
    w = jnp.asarray(topology.ring(n).w(), jnp.float32)
    t = tree(n)
    d0 = float(gossip.consensus_distance(t))
    t = gossip.mix_dense(w, t)
    d1 = float(gossip.consensus_distance(t))
    assert d1 < d0


def test_complete_mix_is_exact_average():
    n = 8
    w = jnp.full((n, n), 1.0 / n)
    t = tree(n)
    mixed = gossip.mix_dense(w, t)
    mean = gossip.node_mean(t)
    for a, b in zip(jax.tree.leaves(mixed), jax.tree.leaves(mean)):
        np.testing.assert_allclose(np.asarray(a),
                                   np.broadcast_to(np.asarray(b), a.shape),
                                   atol=1e-5)


def test_bf16_mix_stays_at_consensus():
    """Regression: ``mix_leaf_dense`` must contract in fp32.  A constant
    bf16 tree is already at consensus; 500 repeated mixes must keep it there
    EXACTLY — casting W to bf16 makes rows sum to 1 +- ~1e-2 and the tree
    drifts off its constant value within a few mixes."""
    n = 16
    w = jnp.asarray(topology.ring(n).w(), jnp.float32)
    const = {"a": jnp.full((n, 6, 4), 0.3017578125, jnp.bfloat16),
             "b": jnp.full((n, 3), -1.1328125, jnp.bfloat16)}

    @jax.jit
    def mix500(t):
        return jax.lax.fori_loop(
            0, 500, lambda _, tr: gossip.mix_dense(w, tr), t)

    out = mix500(const)
    for k in const:
        assert out[k].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(out[k], np.float32),
                                      np.asarray(const[k], np.float32))


_SHARDMAP_SCRIPT = r"""
import os, sys
n = int(sys.argv[1])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
import numpy as np
import jax, jax.numpy as jnp
from repro.core import gossip, topology
from repro.launch.mesh import make_debug_mesh

mesh = make_debug_mesh(shape=(n,), axes=("data",))
topo = topology.ring(n)
w = jnp.asarray(topo.w(), jnp.float32)
k = jax.random.PRNGKey(0)
t = {"a": jax.random.normal(k, (n, 6, 4)),
     "b": jax.random.normal(jax.random.fold_in(k, 1), (n, 3))}

dense = gossip.mix_dense(w, t)
sparse = gossip.mix_sparse_shardmap(t, topology=topo, mesh=mesh,
                                    axis_name="data")
for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(sparse)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
print("SHARDMAP_OK", gossip.compile_gossip_schedule(topo).max_rounds)
"""


@pytest.mark.parametrize("n", [2, 3, 4, 8], ids=lambda n: f"n={n}")
def test_ring_sparse_schedule_equals_dense_mix(n):
    """The compiled ppermute schedule inside ``shard_map`` computes the SAME
    mixing as the dense W einsum for ``ring(n)`` (on n forced host
    devices), including the two smallest rings, where both neighbours are
    one node (n=2) or every node is a neighbour (n=3), and the ring of
    four chips the four-chip cell gossips over."""
    res = subprocess.run(
        [sys.executable, "-c", _SHARDMAP_SCRIPT, str(n)],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=__import__("os").path.dirname(__import__("os").path.dirname(
            __file__)),
    )
    assert "SHARDMAP_OK" in res.stdout, res.stderr[-2000:]
