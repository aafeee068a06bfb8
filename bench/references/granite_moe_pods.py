"""Plain float32 reference of a granite-moe training step on pods that
exchange gradients through the geococo filter.

Each pod's gradient is the mean over its own sequences, from
``granite_moe.row_loss`` (float32, ``precision="highest"``), with the
expert capacity ranked over the pod's own sequences.  Then, per leaf and
per pod, the exchange is written out:

* ``acc = g_p + r_p``, raveled in row-major order and zero-padded to a
  multiple of ``chunk``;
* per chunk, the entries whose ``|acc|`` is at least the
  ``round(density x chunk)``-th largest of the chunk are sent (ties at that
  value, which real gradients do not have, would all be sent);
* ``r_p' = acc - sent_p``, and the synced gradient is the mean of
  ``sent_p`` over pods;
* a leaf of fewer than ``min_leaf_size`` entries is a plain mean over pods
  and keeps a zero residual.

Then AdamW as in ``granite_moe``.

In float32 the parameters, the two moments, a gradient and a residual per
pod do not fit one chip at 8 layers.  So the first device computes the
gradients from the whole parameters, and everything else is held
chunk-major (the exchange's own layout) and spread over the other devices,
where each chunk's filter and the optimizer are local.  With one device
everything lives on it.

``fault`` runs the step wrong in one way, to set a limit's upper reading:
``"mean_first"`` filters the mean over pods (one residual for all),
``"no_exchange"`` lets each pod step on its own gradient (pod 0 is
followed), ``"no_residual"`` feeds no residual back; ``rows`` keeps that
many sequences of each pod's share.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.references.granite_moe import _lr, dims, init_params, leaf_names, row_loss

FAULTS = (None, "mean_first", "no_exchange", "no_residual")


class Reference:
    """The reference for one configuration, optimizer, exchange and batch.

    ``capacity_rows`` is the number of sequences the expert capacity is
    reckoned over: one pod's share of the batch.  ``sync`` holds the
    exchange's ``density``, ``chunk`` and ``min_leaf_size``."""

    def __init__(self, cfg: dict, opt: dict, seq_len: int, capacity_rows: int,
                 sync: dict, n_pods: int, mode: str = "f32", q_block: int = 512):
        n = dims(cfg)
        self.cfg, self.opt, self.n, self.pods = cfg, opt, n, n_pods
        self.chunk = sync["chunk"]
        self.keep = max(1, round(sync["density"] * self.chunk))
        self.min_leaf = sync["min_leaf_size"]
        capacity = max(1, int(cfg["capacity_factor"] * n["k"] * capacity_rows
                              * seq_len / n["e"]))
        devs = jax.devices()
        self.dev = devs[0]
        held = devs[1:] or devs
        self.rows_sharding = NamedSharding(Mesh(np.array(held), ("s",)), P("s"))
        self.spread = len(held)
        self._init = jax.jit(partial(init_params, cfg))
        self._grad = jax.jit(jax.value_and_grad(
            partial(row_loss, cfg, mode, capacity, min(q_block, seq_len)), has_aux=True))
        shapes = jax.eval_shape(self._init, jax.random.PRNGKey(0))
        sizes = [x.size for x in jax.tree.leaves(shapes)]
        self._exchanges = {f: jax.jit(partial(self._exchange, sizes, f), donate_argnums=(0, 1))
                           for f in FAULTS}
        self._adamw_step = jax.jit(self._adamw, donate_argnums=(0, 2, 3))
        self._add = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g), donate_argnums=0)
        self._scale = jax.jit(lambda a, s: jax.tree.map(lambda x: x * s, a),
                              donate_argnums=0)
        self._norms = jax.jit(lambda a: [jnp.linalg.norm(x) for x in jax.tree.leaves(a)])
        self._changes = jax.jit(lambda a, b: [jnp.linalg.norm(x - y) for x, y in
                                              zip(jax.tree.leaves(a), jax.tree.leaves(b))])

    # -- the chunk-major layout -------------------------------------------------

    def _rows(self, size: int) -> int:
        chunks = -(-size // self.chunk)
        return -(-chunks // self.spread) * self.spread

    def to_chunks(self, tree):
        """Each leaf raveled, zero-padded and laid out (rows, chunk) over the
        holding devices."""
        def one(x):
            pad = self._rows(x.size) * self.chunk - x.size
            rows = jnp.pad(x.ravel(), (0, pad)).reshape(-1, self.chunk)
            return jax.device_put(rows, self.rows_sharding)
        return jax.tree.map(one, tree)

    def from_chunks(self, chunks, like):
        """The leaves of ``like``'s shapes back on the first device."""
        return jax.tree.map(
            lambda c, x: jax.device_put(c, self.dev).ravel()[: x.size].reshape(x.shape),
            chunks, like)

    # -- the exchange and the optimizer -------------------------------------------

    def _filter(self, acc):
        mag = jnp.abs(acc)
        kth = jnp.sort(mag, axis=1)[:, self.chunk - self.keep][:, None]
        return jnp.where(mag >= kth, acc, 0.0)

    def _exchange(self, sizes, fault, grads, residuals):
        """(synced gradient, new residual per pod), leaf by leaf."""
        if fault == "mean_first":
            mean = jax.tree.map(lambda *g: sum(g) / self.pods, *grads)
            grads = [mean] * self.pods
            residuals = [residuals[0]] * self.pods
        if fault == "no_residual":
            residuals = [jax.tree.map(jnp.zeros_like, r) for r in residuals]
        flat_g = [jax.tree.leaves(g) for g in grads]
        flat_r = [jax.tree.leaves(r) for r in residuals]
        synced, new = [], [[] for _ in range(self.pods)]
        for i, size in enumerate(sizes):
            gs = [fg[i] for fg in flat_g]
            rs = [fr[i] for fr in flat_r]
            if fault == "no_exchange":
                synced.append(gs[0])
                for p in range(self.pods):
                    new[p].append(rs[p])
                continue
            if size < self.min_leaf:
                synced.append(sum(gs) / self.pods)
                for p in range(self.pods):
                    new[p].append(rs[p])
                continue
            sent = []
            for p in range(self.pods):
                acc = gs[p] + rs[p]
                sent.append(self._filter(acc))
                new[p].append(acc - sent[-1])
            synced.append(sum(sent) / self.pods)
        tdef = jax.tree.structure(grads[0])
        return (jax.tree.unflatten(tdef, synced),
                [jax.tree.unflatten(tdef, r) for r in new])

    def _adamw(self, params, grads, m, v, step):
        opt = self.opt
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        g = jax.tree.map(lambda x: x * clip, grads)
        m = jax.tree.map(lambda m_, g_: opt["b1"] * m_ + (1 - opt["b1"]) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: opt["b2"] * v_ + (1 - opt["b2"]) * g_ * g_, v, g)
        stepf = step.astype(jnp.float32)
        b1c, b2c = 1 - opt["b1"] ** stepf, 1 - opt["b2"] ** stepf
        lr = _lr(opt, stepf)
        params = jax.tree.map(
            lambda p, m_, v_: p - lr * ((m_ / b1c) / (jnp.sqrt(v_ / b2c) + opt["eps"])
                                        + opt["weight_decay"] * p),
            params, m, v)
        return params, m, v, [jnp.linalg.norm(x) for x in jax.tree.leaves(g)]

    # -- the steps ----------------------------------------------------------------

    def train(self, seed: int, batches: list[dict], rows: int | None = None,
              fault: str | None = None) -> dict:
        """Follow one step per batch from the seed's parameters.

        Returns the loss of each step before its update (the mean over
        pods of each pod's mean), the norm of each leaf's clipped first
        synced gradient, the norm of each leaf's change over all the steps
        and, per pod, the norm of each leaf of its residual after them."""
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        n = self.n
        share = batches[0]["tokens"].shape[0] // self.pods
        rows = share if rows is None else rows
        with jax.default_matmul_precision("highest"):
            params = self._init(jax.random.PRNGKey(seed))
            held = self.to_chunks(params)
            p0 = jax.tree.map(jnp.copy, held)
            m = jax.tree.map(jnp.zeros_like, held)
            v = jax.tree.map(jnp.zeros_like, held)
            residuals = [jax.tree.map(jnp.zeros_like, held) for _ in range(self.pods)]
            losses, grad_norms = [], None
            for step, bt in enumerate(batches, start=1):
                grads, total = [], 0.0
                for pod in range(self.pods):
                    offsets = jnp.zeros((n["layers"], n["e"]), jnp.int32)
                    acc = None
                    for r in range(pod * share, pod * share + rows):
                        (loss, counts), g = self._grad(
                            params, jax.device_put(bt["tokens"][r], self.dev),
                            jax.device_put(bt["labels"][r], self.dev), offsets)
                        offsets = offsets + counts
                        g = self.to_chunks(g)
                        acc = g if acc is None else self._add(acc, g)
                        total += float(loss) / rows
                    grads.append(self._scale(acc, jnp.float32(1.0 / rows)))
                losses.append(total / self.pods)
                synced, residuals = self._exchanges[fault](grads, residuals)
                del grads
                held, m, v, clipped = self._adamw_step(held, synced, m, v, jnp.int32(step))
                del synced
                if grad_norms is None:
                    grad_norms = [float(x) for x in clipped]
                params = self.from_chunks(held, params)
            change_norms = [float(x) for x in self._changes(held, p0)]
            res_norms = [[float(x) for x in self._norms(r)] for r in residuals]
        names = leaf_names(params)
        return {"losses": losses,
                "grad_norms": dict(zip(names, grad_norms)),
                "change_norms": dict(zip(names, change_norms)),
                "residual_norms": [dict(zip(names, r)) for r in res_norms]}

