"""Thousand-node scenario demo: 1024 clients, power-law social graph,
client sampling + churn + stragglers, on the node-batched hybrid runtime.

The paper's experiments stop at n=32 fully-participating nodes; this demo
pushes the SAME training engine to n=1024 with realistic failure modes
(DESIGN.md §11):

* topology: generated power-law graph (``powerlaw:2.5``) with Metropolis
  weights — far better spectral gap than a ring at this n;
* participation model: 80% of clients sampled per round, 10% churned out in
  5-step windows, 5% stragglers whose updates miss the gossip round; all
  deterministic under ``scenario.seed``;
* runtime: 8 forced host devices, each carrying a contiguous block of
  b = 1024/8 = 128 nodes — the whole step stays one ``shard_map`` dispatch
  and per-device state is O(n/devices).

Runs on CPU in a couple of minutes:

    PYTHONPATH=src python examples/thousand_node_demo.py
"""
import os

# a CPU study: forced host devices MUST be set before jax initializes
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax                                                # noqa: E402

from repro import api                                     # noqa: E402
from repro.launch.mesh import make_debug_mesh             # noqa: E402

mesh = make_debug_mesh(shape=(8,), axes=("data",))

spec = api.presets.get("n1024_churn").override("loop.steps=20",
                                               "loop.log_every=5")
print(f"platform={jax.devices()[0].platform}  "
      f"{spec.name}: n={spec.topology.n} on {spec.topology.name}, "
      f"participation={spec.scenario.participation}, "
      f"dropout={spec.scenario.dropout} "
      f"(window={spec.scenario.churn_window}), "
      f"straggler={spec.scenario.straggler}")

result = api.run(spec, mesh=mesh)     # runtime='auto' -> hybrid (8 | 1024)

h = result.history[-1]
print(f"\nheterogeneity: mean pairwise TV = "
      f"{result.heterogeneity['mean_tv']:.3f} "
      f"(client sizes {result.heterogeneity['min_client_size']}.."
      f"{result.heterogeneity['max_client_size']})")
print(f"last round: alive {100 * h['alive_frac']:.0f}% of clients, "
      f"{100 * h['mix_frac']:.0f}% reached the gossip round")
print(f"test acc (avg over {spec.topology.n} nodes) = "
      f"{result.final['acc']:.4f}  eval loss = "
      f"{result.final['eval_loss']:.4f}")
