"""Plain reference of decentralized training with quasi-global momentum.

QG-DSGDm-N (Lin et al., ICML 2021, Algorithm 1 with Nesterov momentum), on
each node i with its own batch, every step:

    g'   = grad L_i(x_i) + wd x_i
    m    = beta m_hat_i + g'
    x_i^{1/2} = x_i - lr (beta m + g')
    x_i^+     = sum_j W_ij x_j^{1/2}                (one gossip round)
    m_hat_i   = mu m_hat_i + (1 - mu) (x_i - x_i^+) / lr

``W`` is built here from the topology's definition (Metropolis-Hastings
weights on a ring).  The model's loss comes from the configuration's own
reference module (``bench/configs/<config>.py``).  Nothing of the program is
imported and nothing it made is used: the weights come from the
configuration's ``init_params`` and the seed, the batches are the inputs the
program was fed.

The same code, given other arguments, produces the readings that set the
limits of ``bench/compare.py``: ``dtype=bfloat16`` is the control (the
reference in the precision below the configuration's float32), and
``fault="half_batch"`` / ``fault="no_gossip"`` plant a fault in the
reference put in the program's place.
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np

FAULTS = ("half_batch", "no_gossip")


def ring_mixing(n: int) -> np.ndarray:
    """Metropolis-Hastings weights of an undirected ring of ``n`` nodes."""
    adj = np.zeros((n, n))
    for i in range(n):
        adj[i, (i - 1) % n] = adj[i, (i + 1) % n] = 1.0
    np.fill_diagonal(adj, 0.0)
    deg = adj.sum(1)
    w = np.where(adj > 0, 1.0 / (1.0 + np.maximum(deg[:, None], deg[None])),
                 0.0)
    return w + np.diag(1.0 - w.sum(1))


@dataclasses.dataclass
class Readings:
    """What the comparison reads, from the program or from a reference."""

    losses: list              # node-mean loss of each of the first steps
    first_grad: dict          # leaf path -> norm of W g'_0 over all nodes
    delta: dict               # leaf path -> norm of x_k - x_0 over all nodes
    raw_grad: dict = None     # leaf path -> norm of grad L(x_0) (reference)


_JITTED: dict = {}


def _jit(key, make):
    """One compiled function per key for the life of the process, so that
    repeated reference runs (one per seed) trace and compile once."""
    if key not in _JITTED:
        _JITTED[key] = make()
    return _JITTED[key]


def leaf_norms(tree) -> dict:
    """Per-leaf Euclidean norm of a pytree, by key path (one device call)."""
    import jax
    import jax.numpy as jnp
    paths, leaves = zip(*jax.tree_util.tree_flatten_with_path(tree)[0])
    norms = _jit("norms", lambda: jax.jit(lambda ls: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32)))) for l in ls])))
    vals = norms(list(leaves))
    return dict(zip(map(jax.tree_util.keystr, paths),
                    np.asarray(vals, np.float64).tolist()))


def _combine(per_node: list) -> dict:
    """Norm over all nodes from each node's per-leaf norms."""
    return {k: float(np.sqrt(sum(p[k] ** 2 for p in per_node)))
            for k in per_node[0]}


def _stacked_norms(trees) -> dict:
    """Norm over all nodes of each leaf of a list of per-node trees."""
    return _combine([leaf_norms(t) for t in trees])


def run(model, cfg, traffic, x0, batches, *, dtype="float32", fault=None,
        device=None) -> Readings:
    """Follow ``len(batches)`` steps of the job from the single-node weights
    ``x0`` (every node starts there).  ``batches[t]`` is the node-stacked
    host batch of step t.  float32 runs under 'highest' matmul precision, as
    the configuration states fp32 arithmetic; bfloat16 runs at default."""
    import contextlib

    import jax
    import jax.numpy as jnp

    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    opt = traffic["optimizer"]
    lr, wd = opt["lr"], opt["weight_decay"]
    beta, mu = opt["beta"], opt["mu"]
    n = traffic["nodes"]
    if traffic["topology"] != "ring":
        raise ValueError(f"no reference mixing for {traffic['topology']!r}")
    w = ring_mixing(n)
    if fault == "no_gossip":
        w = np.eye(n)
    dt = jnp.dtype(dtype)
    device = device or jax.devices()[0]
    precision = (jax.default_matmul_precision("highest")
                 if dt == jnp.float32 else contextlib.nullcontext())

    put = lambda a: jax.device_put(a, device)
    x0 = jax.tree.map(lambda a: put(a.astype(dt)), x0)
    hyper = (lr, wd, beta, mu)
    grad_fn = _jit(("grad", id(model), json.dumps(cfg, sort_keys=True)),
                   lambda: jax.jit(jax.value_and_grad(
                       lambda p, b: model.loss(p, b, cfg))))
    half_fn = _jit("half", lambda: jax.jit(lambda h, x, g, m: jax.tree.map(
        lambda x_, g_, m_: x_ - h[0] * (h[2] * (h[2] * m_ + g_ + h[1] * x_)
                                        + g_ + h[1] * x_), x, g, m)))
    mix_fn = _jit("mix", lambda: jax.jit(lambda ws, halves: jax.tree.map(
        lambda *ls: sum(wi * l for wi, l in zip(ws, ls)), *halves)))
    buf_fn = _jit("buf", lambda: jax.jit(lambda h, m, x, xp: jax.tree.map(
        lambda m_, x_, xp_: h[3] * m_ + (1 - h[3]) * (x_ - xp_) / h[0],
        m, x, xp)))
    delta_fn = _jit("delta", lambda: jax.jit(lambda x, x0_: jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), x, x0_)))

    xs = [x0] * n
    m_hat = [jax.tree.map(jnp.zeros_like, x0)] * n
    losses, raw_grad, first_grad = [], None, None
    with precision:
        for t, batch in enumerate(batches):
            step_loss, halves, grads = [], [], []
            for i in range(n):
                b = tuple(put(np.asarray(a[i])) for a in batch)
                if fault == "half_batch":
                    b = tuple(a[: a.shape[0] // 2] for a in b)
                loss, g = grad_fn(xs[i], b)
                step_loss.append(float(loss))
                if t == 0:
                    grads.append(leaf_norms(g))
                halves.append(half_fn(hyper, xs[i], g, m_hat[i]))
                del g
            if t == 0:
                raw_grad = _combine(grads)
            new_xs = [mix_fn(tuple(w[i, j] for j in range(n) if w[i, j]),
                             [halves[j] for j in range(n) if w[i, j]])
                      for i in range(n)]
            del halves
            m_hat = [buf_fn(hyper, m_hat[i], xs[i], new_xs[i])
                     for i in range(n)]
            xs = new_xs
            losses.append(float(np.mean(step_loss)))
            if t == 0:
                scale = 1.0 / ((1.0 - mu) * (1.0 + beta))
                first_grad = {k: v * scale
                              for k, v in _stacked_norms(m_hat).items()}
        delta = _stacked_norms([delta_fn(x, x0) for x in xs])
    return Readings(losses=losses, first_grad=first_grad, delta=delta,
                    raw_grad=raw_grad)
