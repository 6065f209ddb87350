"""The plain reference of a federated round, and the comparison that decides
``correct``.

Nothing here imports the program. A configuration's model (its loss and the
initial weights) lives in ``configs/<name>_ref.py``; this file holds what the
configurations share: the aggregate client gradient in blocks, the FetchSGD
count sketch re-derived from its definition (chunked-cyclic bucket hash,
murmur3 sign hash, both drawn from the seed), the two server rules the cells
use, the learning-rate schedules, and the per-leaf comparison.

Everything runs in float32 with matmul precision ``highest``, after the timed
window has closed and the program's state is freed.
"""

from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128


# -------------------------------------------------------------------------
# count sketch, from its definition (ops/sketch.py docstring): coordinate i
# sits in chunk t = i // c_pad at position i % c_pad; row j maps it to bucket
# (position + m[j, t]) % c_pad with sign fmix32(i ^ key_j) & 1.
# -------------------------------------------------------------------------

def sketch_geometry(d: int, c: int, r: int, seed: int) -> dict:
    c_pad = -(-int(c) // LANES) * LANES
    T = max(1, -(-int(d) // c_pad))
    rng = np.random.RandomState(seed)
    shifts = rng.randint(0, c_pad, size=(r, T))
    keys = rng.randint(1, 2**31 - 1, size=(r,))
    return {"d": int(d), "c_pad": c_pad, "T": T, "r": int(r),
            "shifts": jnp.asarray(shifts, jnp.int32),
            "keys": jnp.asarray(keys, jnp.uint32)}


def _fmix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _signs(geom, t, key):
    idx = (t * geom["c_pad"] + jnp.arange(geom["c_pad"])).astype(jnp.uint32)
    return (_fmix32(idx ^ key) & 1).astype(jnp.float32) * 2.0 - 1.0


def _chunks(geom, v):
    pad = geom["T"] * geom["c_pad"] - geom["d"]
    return jnp.pad(v.astype(jnp.float32), (0, pad)).reshape(
        geom["T"], geom["c_pad"])


def sketch(geom, v):
    """(d,) vector -> (r, c_pad) table."""
    chunks = _chunks(geom, v)

    def row(key, shifts):
        def body(acc, xs):
            t, chunk, m = xs
            return acc + jnp.roll(chunk * _signs(geom, t, key), m), None

        acc, _ = jax.lax.scan(
            body, jnp.zeros(geom["c_pad"], jnp.float32),
            (jnp.arange(geom["T"]), chunks, shifts))
        return acc

    return jnp.stack([row(geom["keys"][j], geom["shifts"][j])
                      for j in range(geom["r"])])


def estimates(geom, table):
    """(r, c_pad) table -> (d,) median-of-rows estimate of every coordinate."""

    def row(tbl, key, shifts):
        def body(_, xs):
            t, m = xs
            return None, jnp.roll(tbl, -m) * _signs(geom, t, key)

        _, est = jax.lax.scan(body, None, (jnp.arange(geom["T"]), shifts))
        return est.reshape(-1)[:geom["d"]]

    rows = jnp.stack([row(table[j], geom["keys"][j], geom["shifts"][j])
                      for j in range(geom["r"])])
    return jnp.median(rows, axis=0)


def topk_mask(v, k: int):
    """Keep every entry whose magnitude reaches the k-th largest."""
    mag = jnp.abs(v)
    thr = jnp.sort(mag)[v.shape[0] - min(k, v.shape[0])]
    return jnp.where(mag >= thr, v, 0.0)


# -------------------------------------------------------------------------
# the control's precision: operands of every matmul and convolution rounded
# to 8 bits, scaled per tensor so that its largest entry sits at the format's
# largest (float8 e4m3: 448; int8: 127), products accumulated in float32.
# Straight-through: the backward pass sees the rounded operands, its
# cotangents stay float32.
# -------------------------------------------------------------------------

def lowp(x, cast):
    """``x`` as a matmul operand in the precision ``cast`` (None: as is)."""
    if cast is None:
        return x
    top = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if cast == "fp8":
        scale = 448.0 / top
        q = (x * scale).astype(jnp.float8_e4m3fn).astype(x.dtype) / scale
    elif cast == "int8":
        scale = 127.0 / top
        q = jnp.round(x * scale) / scale
    else:
        raise ValueError(f"unknown control precision {cast!r}")
    return x + jax.lax.stop_gradient(q - x)


# -------------------------------------------------------------------------
# server rules
# -------------------------------------------------------------------------

class SketchServer:
    """FetchSGD: momentum and error feedback in sketch space."""

    def __init__(self, d, traffic, seed):
        self.geom = sketch_geometry(d, traffic["num_cols"],
                                    traffic["num_rows"], seed)
        self.k = int(traffic["k"])
        self.rho = float(traffic["virtual_momentum"])
        shape = (self.geom["r"], self.geom["c_pad"])
        self.u = jnp.zeros(shape, jnp.float32)
        self.v = jnp.zeros(shape, jnp.float32)
        self._step = jax.jit(self._rule)
        self.transmit = jax.jit(lambda g: sketch(self.geom, g))

    def _rule(self, table, u, v, w, lr):
        u = table + self.rho * u
        v = v + u
        update = topk_mask(estimates(self.geom, v), self.k)
        hit = sketch(self.geom, update) != 0
        return (w - lr * update, jnp.where(hit, 0.0, u),
                jnp.where(hit, 0.0, v))

    def step(self, transmit, w, lr):
        w, self.u, self.v = self._step(transmit, self.u, self.v, w, lr)
        return w

    @staticmethod
    def leaves(transmit):
        return [transmit[j] for j in range(transmit.shape[0])]


class DenseServer:
    """Uncompressed: momentum SGD on the averaged gradient."""

    def __init__(self, d, traffic, seed):
        del seed
        self.rho = float(traffic["virtual_momentum"])
        self.vel = jnp.zeros(d, jnp.float32)
        self.transmit = lambda g: g

    def step(self, transmit, w, lr):
        self.vel = transmit + self.rho * self.vel
        return w - lr * self.vel

    leaves = None  # the model's own leaves


SERVERS = {"sketch": SketchServer, "uncompressed": DenseServer}


# -------------------------------------------------------------------------
# learning-rate schedules of the two entry points (step s is 1-based: the
# scheduler steps before each round)
# -------------------------------------------------------------------------

def lr_at(schedule: dict, step: int, steps_per_epoch: int) -> float:
    if schedule["kind"] == "triangle":  # cv_train: 0 -> peak at pivot -> 0
        knots = [0, schedule["pivot_epoch"], schedule["num_epochs"]]
        vals = [0, schedule["lr_scale"], 0]
        return float(np.interp([step / steps_per_epoch], knots, vals)[0])
    if schedule["kind"] == "linear_decay":  # gpt2_train: peak -> 0
        knots = [0, schedule["num_epochs"] * steps_per_epoch]
        return float(np.interp([step], knots, [schedule["lr_scale"], 0])[0])
    raise ValueError(f"unknown schedule {schedule['kind']!r}")


# -------------------------------------------------------------------------
# the aggregate client gradient, in blocks of clients
# -------------------------------------------------------------------------

def round_gradient(model, params, batch, weight_decay, num_workers,
                   block_clients, cast=None):
    """Data-weighted mean gradient of one round plus the clients' weight
    decay, and the per-client mean losses. ``batch`` is the loader's
    client-major dict (numpy). ``cast`` computes the model's matmuls in a
    lower precision (the control, see ``lowp``)."""
    wmask = np.asarray(batch["worker_mask"], np.float32)
    W = wmask.shape[0]
    total = float(np.sum(np.asarray(batch["mask"]) *
                         wmask.reshape((W,) + (1,) * (batch["mask"].ndim - 1))))

    def block_loss(p, blk):
        sums, counts = jax.vmap(lambda b: model.loss_sum(p, b, cast))(blk)
        sums = sums.astype(jnp.float32) * blk["worker_mask"]
        return jnp.sum(sums), (sums, counts)

    grad_fn = jax.jit(jax.value_and_grad(block_loss, has_aux=True))
    g_sum = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    for lo in range(0, W, block_clients):
        blk = {k: jnp.asarray(np.asarray(v)[lo:lo + block_clients])
               for k, v in batch.items() if k != "client_ids"}
        (_, (sums, counts)), g = grad_fn(params, blk)
        g_sum = jax.tree_util.tree_map(
            lambda a, b: a + b.astype(jnp.float32), g_sum, g)
        losses.append(np.asarray(sums) / np.maximum(np.asarray(counts), 1.0))
    losses = np.concatenate(losses)[wmask > 0].astype(np.float64)
    scale = weight_decay / num_workers
    grad = jax.tree_util.tree_map(
        lambda g, p: g / max(total, 1.0) + scale * p, g_sum, params)
    return grad, losses


# -------------------------------------------------------------------------
# the comparison
# -------------------------------------------------------------------------

def leaf_norms(leaves):
    return np.asarray([float(jnp.linalg.norm(x.astype(jnp.float32)))
                       for x in leaves], np.float64)


def leaf_gaps(prog, ref):
    """Gap between the program's and the reference's norm of each leaf,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = statistics.median(ref.tolist())
    return np.abs(prog - ref) / np.maximum(np.maximum(ref, floor), 1e-30)


def worst_leaf_gap(prog, ref, keep=None):
    """Largest of ``leaf_gaps`` over the leaves that count."""
    gaps = leaf_gaps(prog, ref)
    if keep is not None:
        gaps = gaps[np.asarray(keep)]
    return float(np.max(gaps)) if gaps.size else 0.0


def follow(model, traffic, seed, batches, steps_per_epoch, block_clients,
           cast=None):
    """Run the reference through ``len(batches)`` rounds from the seed's
    weights. Returns the losses, the leaf norms of the first transmit and of
    the parameters' change, and which leaves count for the change."""
    from jax.flatten_util import ravel_pytree

    with jax.default_matmul_precision("highest"):
        params = model.init(seed)
        flat0, unravel = ravel_pytree(params)
        server = SERVERS[traffic["mode"]](flat0.shape[0], traffic,
                                          traffic["program_seed"])
        w = flat0
        client_losses, first_leaves, grad_leaf = [], None, None
        for s, batch in enumerate(batches, start=1):
            grad, losses = round_gradient(
                model, unravel(w), batch, traffic["weight_decay"],
                traffic["num_workers"], block_clients, cast)
            client_losses.append(losses)
            g_flat = ravel_pytree(grad)[0]
            transmit = server.transmit(g_flat)
            if s == 1:
                grad_leaf = leaf_norms(jax.tree_util.tree_leaves(grad))
                first_leaves = leaf_norms(
                    server.leaves(transmit) if server.leaves
                    else jax.tree_util.tree_leaves(grad))
            lr = lr_at(traffic["schedule"], s, steps_per_epoch)
            w = server.step(transmit, w, lr)
            del grad, g_flat, transmit
        change = leaf_norms(jax.tree_util.tree_leaves(unravel(w - flat0)))
    # a leaf whose gradient is nought to rounding moves by round-off alone
    keep = grad_leaf >= 1e-3 * statistics.median(grad_leaf.tolist())
    return {"client_losses": client_losses, "first": first_leaves,
            "change": change, "keep": keep}


def worst_leaves(prog, ref, names, n=4):
    """The look: the n leaves with the widest gap, as (name, program's norm,
    reference's norm)."""
    order = np.argsort(-leaf_gaps(prog, ref))[:n]
    return [(names[i] if names else str(i), float(prog[i]), float(ref[i]))
            for i in order] + [("median leaf", float(np.median(prog)),
                                float(np.median(ref)))]


def compare(program: dict, ref: dict, limits: dict) -> dict:
    """Each number compared beside its limit; ``correct`` iff all hold."""
    numbers = {}
    for i, (lp, lr_) in enumerate(zip(program["client_losses"],
                                      ref["client_losses"]), 1):
        lp, lr_ = np.asarray(lp, np.float64), np.asarray(lr_, np.float64)
        # the round's loss, and the worst client's: the mean over a round's
        # clients averages rounding away, one client's few examples do not
        numbers[f"loss{i}_gap"] = abs(lp.mean() - lr_.mean()) / max(
            abs(lr_.mean()), 1e-30)
        # a client whose loss the program never reported reads as a gap of 1
        numbers[f"closs{i}_gap"] = (worst_leaf_gap(lp, lr_)
                                    if lp.shape == lr_.shape else 1.0)
    numbers["grad1_gap"] = worst_leaf_gap(program["first"], ref["first"])
    numbers["change3_gap"] = worst_leaf_gap(program["change"], ref["change"],
                                            ref["keep"])
    # all leaves together: where a k-sparse update leaves a small leaf a
    # handful of coordinates, the worst leaf is the noise of that handful
    pc = np.linalg.norm(np.asarray(program["change"], np.float64)[ref["keep"]])
    rc = np.linalg.norm(np.asarray(ref["change"], np.float64)[ref["keep"]])
    numbers["change3_all_gap"] = float(abs(pc - rc) / max(rc, 1e-30))
    table = {name: {"value": float(val), "limit": float(limits[name])}
             for name, val in numbers.items() if name in limits}
    ok = all(math.isfinite(e["value"]) and e["value"] <= e["limit"]
             for e in table.values()) and bool(table)
    return {"correct": ok, "compared": table,
            "not_compared": {n: float(v) for n, v in numbers.items()
                             if n not in limits}}
