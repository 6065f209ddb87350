"""Mixture-of-Experts MLP with expert parallelism over an ``expert`` mesh axis.

Extension beyond the reference (SURVEY.md §2.3: the reference's only
parallelism is data-parallel client simulation; MoE/expert parallelism is
explicitly absent there). This gives the GPT-2 workload a GShard/Switch-style
sparsely-activated MLP whose experts shard across TPU cores:

- **Routing**: top-1 (Switch) — a linear router scores every token against
  every expert; each token is combined with its argmax expert's output,
  weighted by that expert's softmax probability (so the router receives
  gradient through the selected probability).
- **Dispatch** (``dispatch=``): two modes.
  ``dense`` (default) — every expert evaluates all tokens and the combine
  weights zero the non-routed ones. No token dropping, no capacity
  factor, one big batched einsum the MXU tiles well — but every token
  pays all ``E/ne`` local experts' MLP FLOPs.
  ``sparse`` — GShard/Switch capacity-factor dispatch: each expert
  processes only the tokens argmax-routed to it, up to a static capacity
  ``Cap = round(capacity_factor * N / E)`` per expert; overflow tokens
  are DROPPED from the MoE output (their residual stream passes through
  unchanged, the Switch semantics). Tokens move through one-hot dispatch
  matmuls (the standard TPU formulation: static shapes, MXU-friendly),
  cutting expert-MLP FLOPs by ``E / capacity_factor`` at the cost of the
  two ``N x (E*Cap) x C`` dispatch/combine einsums. At ``capacity_factor
  >= E`` no token can drop and the output equals dense dispatch exactly
  (same selected-expert outputs and gates) — the parity contract
  ``tests/test_moe.py`` pins.
- **Expert parallelism** (``expert_axis``): parameters stay FULL-SHAPE and
  replicated — identical tree/layout whether or not the mesh has an
  ``expert`` axis — so the federated flat vector, compression, and
  checkpoints never see expert parallelism (same contract as
  ``models.gpt2.TPDense``). Each shard dynamic-slices its expert block,
  computes the partial combine over its local experts, and one
  ``psum`` reassembles the full MoE output. Gradients: expert-sliced
  params get slice-local grads (zero outside the shard's slice — the psum
  in the worker reassembles them, scale 1); the router and all non-MoE
  params are computed identically on every shard (scale 1/ne). See
  ``ep_sliced_param`` and ``federated/rounds.py`` ``ep_scale``.

The Switch auxiliary load-balancing loss (E·Σ f·P) is sown into the
``moe_losses`` collection per MoE layer and added to the training loss by
``losses.make_gpt2_losses`` when ``--moe_aux_coef`` > 0 (under dense
dispatch imbalance is a routing-quality concern; under sparse dispatch it
additionally controls the overflow-drop rate, so keep it on there).

``RoutedMoE`` is the layer of the DeepSeek-V3 family (models/joyai.py, and
models/laguna.py without the selection bias):
sigmoid scores, the ``top_k`` experts by ``score + router_bias``, gates
renormalised over the selected and scaled, a shared expert beside the routed
ones. It is *told which experts it holds* (``n_held`` from ``expert_offset``
on): it routes over all ``n_routed`` and returns what its own experts give
plus the shared expert; what absent experts would add is left out (one
chip's share of an expert-parallel deployment, without the exchange). No
token is dropped: ``routed_experts`` groups the (token, expert) pairs whose
expert is held by expert, runs one grouped matrix product a projection
(``jax.lax.ragged_dot``) and adds the gated rows back. Its shapes are
static: a ladder of row counts from the token count doubled up to the
worst case (every pair held), the smallest rung that holds the call's pairs
chosen on the device (``lax.switch``); within a rung the grouped products
skip the row tiles no pair fills, so the work follows the pairs that are
there.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

__all__ = ["MoEMLP", "RoutedMoE", "routed_experts", "ep_sliced_param"]


def ep_sliced_param(path: str) -> bool:
    """True for parameters whose per-shard gradients SUM to the full
    gradient across expert shards (psum with scale 1): the expert-stacked
    MLP weights/biases (leading expert dim sliced, disjoint) AND the
    router — each shard's router grad is the backprop of only its local
    experts' combine weights (disjoint cotangent slices in prob space, so
    the per-shard contributions are partial and sum exactly; the softmax
    Jacobian makes them dense but not replicated). ``path`` is the
    '/'-joined lowercase flat-param path."""
    return "/moe/" in path or path.startswith("moe/")


class MoEMLP(nn.Module):
    """Top-1-routed mixture-of-experts MLP (drop-in for a transformer
    block's dense MLP; see module docstring for routing/dispatch/sharding
    semantics)."""

    n_embd: int
    n_experts: int
    expert_axis: Optional[str] = None
    # Bound sequence-parallel mesh axis, when the block runs inside a
    # seq shard_map (Block passes it for ring/ulysses attention). Routing
    # and dispatch are per-token and need no communication, but the
    # load-balancing aux must use GLOBAL routing statistics: f/P are
    # globalized over this axis (psum_repct/nsq) so the sown aux is
    # replicated across seq shards (the loss contract of
    # losses.make_gpt2_losses) and its psum'ed gradient is exact.
    # COMPOSES with expert_axis (a clients x seq x expert mesh): each
    # (seq, expert) shard dispatches its local tokens to its local
    # experts; the two reconciliations (seq psum at scale 1, expert psum
    # x ep_scale) act on orthogonal axes.
    seq_axis: Optional[str] = None
    # "dense" | "sparse" — see module docstring. Under seq parallelism the
    # sparse capacity is per seq shard (cf * N_local / E): a different
    # (equally valid) drop rule than global capacity, needing no
    # cross-shard communication.
    dispatch: str = "dense"
    capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x):
        assert self.dispatch in ("dense", "sparse"), \
            f"unknown dispatch {self.dispatch!r}"
        # x: (B, T, C)
        C, E = self.n_embd, self.n_experts
        router = self.param("router", nn.initializers.normal(0.02), (C, E))
        w_fc = self.param("w_fc", nn.initializers.normal(0.02),
                          (E, C, 4 * C))
        b_fc = self.param("b_fc", nn.initializers.zeros, (E, 4 * C))
        w_proj = self.param("w_proj", nn.initializers.normal(0.02),
                            (E, 4 * C, C))
        b_proj = self.param("b_proj", nn.initializers.zeros, (E, C))

        if self.expert_axis is not None:
            # Megatron f operator BEFORE the router so that the input
            # cotangent from BOTH consumers of x (router path and expert
            # path) rides the backward psum — everything upstream then
            # sees the same replicated gradient as the unsharded module
            from commefficient_tpu.ops.collectives import ident_psumct

            x = ident_psumct(x, self.expert_axis)

        # routing in f32 for a stable softmax regardless of compute dtype
        logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)            # (B, T, E)
        top = jnp.argmax(probs, axis=-1)                   # (B, T)
        oh = jax.nn.one_hot(top, E, dtype=probs.dtype)     # (B, T, E)
        # top-1 combine weights: the selected expert's probability (router
        # grad flows through the selected prob; the argmax one-hot is a
        # constant, the Switch-transformer estimator)
        combine = (oh * probs).astype(x.dtype)             # (B, T, E)

        if self.expert_axis is None:
            e0, e_loc = 0, E
        else:
            ne = jax.lax.psum(1, self.expert_axis)
            assert E % ne == 0, \
                f"n_experts {E} must divide by the expert axis size {ne}"
            e_loc = E // ne
            e0 = jax.lax.axis_index(self.expert_axis) * e_loc

        def sl(p, axis=0):
            return jax.lax.dynamic_slice_in_dim(p, e0, e_loc, axis=axis)

        # Switch load-balancing auxiliary loss, aux = E·Σ_e f_e·P_e
        # (f_e: fraction of tokens argmax-routed to expert e; P_e: mean
        # router probability of e; minimum 1.0 at perfect balance).
        # Computed from the LOCAL expert slice and psum'ed so that under
        # expert parallelism its router gradients are disjoint partial
        # contributions — exactly the scale-1 contract of ep_sliced_param
        # (a replicated aux would overcount the aux grads by ne).
        # Sown into the "moe_losses" collection: free unless the caller
        # applies with mutable=["moe_losses"] (losses.make_gpt2_losses
        # does when moe_aux_coef > 0).
        f_loc = jnp.mean(sl(oh, axis=2), axis=(0, 1))          # (E_loc,)
        p_loc = jnp.mean(sl(probs, axis=2), axis=(0, 1))       # (E_loc,)
        if self.seq_axis is not None:
            # global routing stats: each seq shard sees T/nsq of the
            # tokens, so the global means are the mean of the local ones;
            # aux becomes replicated across seq shards. _psum_repct (psum
            # forward, identity backward) + explicit /nsq rather than
            # pmean: each shard's gradient contribution through its local
            # stats is then 1/nsq of the replicated cotangent, which the
            # worker's seq-axis grad psum sums back to exactly the full
            # gradient — independent of how JAX transposes a plain psum
            # under shard_map (see ops/collectives.py).
            from commefficient_tpu.ops.collectives import psum_repct

            nsq = jax.lax.psum(1, self.seq_axis)
            f_loc = psum_repct(f_loc, self.seq_axis) / nsq
            p_loc = psum_repct(p_loc, self.seq_axis) / nsq
        aux = float(E) * jnp.sum(f_loc * p_loc)
        if self.expert_axis is not None:
            from commefficient_tpu.ops.collectives import psum_repct

            aux = psum_repct(aux, self.expert_axis)
        self.sow("moe_losses", "aux", aux)

        if self.dispatch == "sparse":
            out = self._sparse_dispatch(x, top, combine, sl,
                                        (w_fc, b_fc, w_proj, b_proj))
        else:
            # dense dispatch over the shard's local experts: (E_loc,B,T,·)
            h = jnp.einsum("btc,ecf->ebtf", x, sl(w_fc)) \
                + sl(b_fc)[:, None, None, :]
            h = nn.gelu(h, approximate=True)
            y = jnp.einsum("ebtf,efc->ebtc", h, sl(w_proj)) \
                + sl(b_proj)[:, None, None, :]
            out = jnp.einsum("bte,ebtc->btc", sl(combine, axis=2), y)
        if self.expert_axis is not None:
            # g operator: psum fwd (partial combines -> full MoE output),
            # identity bwd (the output cotangent is replicated)
            from commefficient_tpu.ops.collectives import psum_repct

            out = psum_repct(out, self.expert_axis)
        return out

    def _sparse_dispatch(self, x, top, combine, sl, params):
        """Capacity-factor dispatch: route each token to its argmax
        expert's queue slot, process only the ``Cap`` queued tokens per
        expert, and combine back gated by the selected probability.
        Overflow tokens (queue position >= Cap) get an all-zero dispatch
        row and fall out of the MoE output (residual passthrough)."""
        w_fc, b_fc, w_proj, b_proj = params
        B, T, C = x.shape
        E = self.n_experts
        N = B * T
        cap = max(1, int(round(self.capacity_factor * N / E)))
        xf = x.reshape(N, C)
        sel = top.reshape(N)                                     # (N,)
        # queue position of each token within its expert, in token order
        ohs = jax.nn.one_hot(sel, E, dtype=jnp.int32)            # (N, E)
        pos = jnp.sum((jnp.cumsum(ohs, axis=0) - 1) * ohs, axis=1)
        # one_hot of an out-of-range position is an all-zero row: tokens
        # beyond capacity vanish from D with no explicit mask
        de = jax.nn.one_hot(sel, E, dtype=x.dtype)               # (N, E)
        dp = jax.nn.one_hot(pos, cap, dtype=x.dtype)             # (N, Cap)
        d = de[:, :, None] * dp[:, None, :]                      # (N,E,Cap)
        # local expert slice of the dispatch tensor (same e0 as sl())
        d_loc = sl(jnp.moveaxis(d, 1, 0))                        # (E_loc,N,Cap)
        xin = jnp.einsum("enp,nc->epc", d_loc, xf)               # (E_loc,Cap,C)
        h = jnp.einsum("epc,ecf->epf", xin, sl(w_fc)) \
            + sl(b_fc)[:, None, :]
        h = nn.gelu(h, approximate=True)
        y = jnp.einsum("epf,efc->epc", h, sl(w_proj)) \
            + sl(b_proj)[:, None, :]
        # gate = the selected expert's probability (combine rows are
        # one-hot x prob, so the row-sum is exactly that scalar)
        gate = jnp.sum(combine, axis=-1).reshape(N, 1)           # (N, 1)
        out = jnp.einsum("enp,epc->nc", d_loc, y) * gate
        return out.reshape(B, T, C)


# -- routed experts, a share of them held here (no dropped token) -----------

def _rows_by_expert(e_local, n_held: int, n_rows: int):
    """The held (token, expert) pairs laid out in ``n_rows`` rows, grouped by
    expert and in token order within one: ``(token, expert, valid)`` of every
    row and the groups' sizes. ``e_local`` is (N, k): the local index of each
    slot's expert, -1 where it is absent. No sort and no scatter: a row's
    pair is found by bisection in the running count of an (expert, token)
    mask. Rows past the last pair are not valid."""
    n_tok = e_local.shape[0]
    held = (e_local[None] == jnp.arange(n_held)[:, None, None]).any(-1)
    sizes = jnp.sum(held, axis=1, dtype=jnp.int32)                 # (E,)
    count = jnp.cumsum(held.reshape(-1).astype(jnp.int32))         # (E*N,)
    flat = jnp.searchsorted(count, jnp.arange(1, n_rows + 1, dtype=jnp.int32),
                            method="scan_unrolled")
    valid = jnp.arange(n_rows) < count[-1]
    flat = jnp.minimum(flat, n_held * n_tok - 1)
    return flat % n_tok, flat // n_tok, valid, sizes


def _grouped_dot(operand_dtype):
    """``jax.lax.ragged_dot`` (rows (M, K) grouped by ``sizes`` times
    (G, K, N)) with both multiplicands of every product, forward and
    backward, in ``operand_dtype`` and float32 results: what the unit does to
    a plain float32 product at the default precision. XLA:TPU does not do it
    to a grouped product's float32 operands by itself and then runs it 2.4x
    slower (PERF.md, PR 28). ``None`` leaves the operands alone."""
    if operand_dtype is None:
        return jax.lax.ragged_dot
    from jax.lax import RaggedDotDimensionNumbers, ragged_dot_general

    def rnd(a):
        return a.astype(operand_dtype)

    by_rows = RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=(0,), rhs_group_dimensions=())

    @jax.custom_vjp
    def dot(rows, w, sizes):
        return jax.lax.ragged_dot(rnd(rows), rnd(w), sizes,
                                  preferred_element_type=jnp.float32)

    def fwd(rows, w, sizes):
        return dot(rows, w, sizes), (rows, w, sizes)

    def bwd(res, ct):
        rows, w, sizes = res
        d_rows = jax.lax.ragged_dot(rnd(ct), rnd(w).swapaxes(1, 2), sizes,
                                    preferred_element_type=jnp.float32)
        d_w = ragged_dot_general(rnd(rows), rnd(ct), sizes, by_rows,
                                 preferred_element_type=jnp.float32)
        return d_rows.astype(rows.dtype), d_w.astype(w.dtype), None

    dot.defvjp(fwd, bwd)
    return dot


def _experts_on_rows(n_rows, x, gate, e_local, w_gate, w_up, w_down,
                     operand_dtype=None):
    """One rung of the ladder: the held pairs in ``n_rows`` rows through
    their experts' SwiGLU, gated and summed back onto their tokens."""
    with jax.named_scope("fed_moe_route"):
        tok, exp, valid, sizes = _rows_by_expert(e_local, w_gate.shape[0],
                                                 n_rows)
        row_gate = jnp.sum(jnp.where(e_local[tok] == exp[:, None], gate[tok],
                                     0.0), axis=-1)
        # a grouped product leaves the rows past its last group unspecified
        # (and so their cotangents): select on both sides, never multiply
        rows = jnp.where(valid[:, None], x[tok], 0.0)
    with jax.named_scope("fed_moe_experts"):
        dot = _grouped_dot(operand_dtype)
        h = jax.nn.silu(dot(rows, w_gate, sizes)) * dot(rows, w_up, sizes)
        y = dot(h, w_down, sizes)
    with jax.named_scope("fed_moe_route"):
        y = jnp.where(valid[:, None], y, 0.0) \
            * row_gate[:, None].astype(y.dtype)
        return jnp.zeros_like(x).at[tok].add(y)


def routed_experts(x, gate, e_local, w_gate, w_up, w_down,
                   operand_dtype=None):
    """``sum_s gate[t, s] * SwiGLU_{e_local[t, s]}(x[t])`` over the slots
    whose expert is held (``e_local >= 0``): x (N, C), gate and e_local
    (N, k), weights (E, C, F) / (E, F, C). Every pair is computed whatever
    the imbalance. The row count is the smallest rung of a ladder of static
    sizes that holds the pairs present: N rows (k * E_held / E_routed of N
    are expected, so a balanced call has room to spare), doubled up to the
    worst case N * min(k, E). With rungs below N the gathers' and
    scatter-adds' rows made a round's time follow the seed's routing by
    1.3 us a pair; from N up it does so by 0.4 (PERF.md, PR 28).
    The backward pass picks its rung the same way and recomputes the rung's
    forward inside it: nothing of a rung's size is kept between the passes,
    and no rung pays for another's residuals. ``operand_dtype``: see
    ``_grouped_dot``."""
    n_tok, k = e_local.shape
    worst = n_tok * min(k, w_gate.shape[0])
    ladder = [min(n_tok, worst)]
    while ladder[-1] < worst:
        ladder.append(min(2 * ladder[-1], worst))

    def rung(e_local):
        pairs = jnp.sum(e_local >= 0)
        return jnp.sum(pairs > jnp.asarray(ladder[:-1], jnp.int32)) \
            if len(ladder) > 1 else jnp.int32(0)

    @jax.custom_vjp
    def run(x, gate, w_gate, w_up, w_down, e_local):
        return jax.lax.switch(
            rung(e_local),
            [functools.partial(_experts_on_rows, m,
                               operand_dtype=operand_dtype) for m in ladder],
            x, gate, e_local, w_gate, w_up, w_down)

    def fwd(*args):
        return run(*args), args

    def bwd(args, ct):
        def back(m):
            def pull(x, gate, w_gate, w_up, w_down, e_local, ct):
                _, vjp = jax.vjp(
                    lambda x, gate, *w: _experts_on_rows(
                        m, x, gate, e_local, *w,
                        operand_dtype=operand_dtype),
                    x, gate, w_gate, w_up, w_down)
                return vjp(ct)
            return pull

        return jax.lax.switch(rung(args[-1]), [back(m) for m in ladder],
                              *args, ct) + (None,)

    run.defvjp(fwd, bwd)
    return run(x, gate, w_gate, w_up, w_down, e_local)


class SwiGLU(nn.Module):
    """``(silu(x W_gate) * x W_up) W_down``, no biases."""

    width: int

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.normal(0.02)
        dense = functools.partial(nn.Dense, use_bias=False, kernel_init=init)
        h = nn.silu(dense(self.width, name="gate")(x)) \
            * dense(self.width, name="up")(x)
        return dense(x.shape[-1], name="down")(h)


class RoutedMoE(nn.Module):
    """Sigmoid-scored top-k routed experts, ``n_held`` of ``n_routed`` held
    here from ``expert_offset`` on, plus one shared expert (module
    docstring). Returns ``(y, stats)``; ``stats`` are counts for telemetry
    and carry no gradient: the held pairs of every token (``local``, shaped
    like x without its last axis) and the largest held expert's load in this
    call (``max_load``)."""

    n_routed: int
    n_held: int
    expert_offset: int
    top_k: int
    width: int
    scale: float
    # the multiplicands' type in the grouped products (``_grouped_dot``);
    # whoever builds the model knows the backend and the precision
    operand_dtype: Optional[Any] = None
    # DeepSeek-V3's ``router_bias`` leaf, added to the scores in the
    # selection; a model without one (models/laguna.py) has no such leaf
    selection_bias: bool = True

    @nn.compact
    def __call__(self, x):
        assert 0 <= self.expert_offset \
            and self.expert_offset + self.n_held <= self.n_routed, \
            "the held experts must lie inside the routed ones"
        C, F, E = x.shape[-1], self.width, self.n_held
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (C, self.n_routed))
        # e_score_correction_bias: takes part in the selection only, so its
        # gradient is nought; the loss-free balancing update is not run
        bias = self.param("router_bias", nn.initializers.normal(0.01),
                          (self.n_routed,)) if self.selection_bias else None
        w_gate = self.param("w_gate", init, (E, C, F))
        w_up = self.param("w_up", init, (E, C, F))
        w_down = self.param("w_down", init, (E, F, C))
        xf = x.reshape(-1, C)
        with jax.named_scope("fed_moe_route"):
            # float32 products for the scores (six bf16 passes of the unit,
            # 1/500 of the layer's work): the selection below is discrete,
            # and a score rounded to bf16 picks other experts
            score = jax.nn.sigmoid(jnp.dot(
                xf.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            _, idx = jax.lax.top_k(
                score if bias is None else score + bias, self.top_k)
            # the selected scores by mask, not by a gather (a gather of 8
            # of 256 a token and its scatter-add cost 11 ms a round on the
            # v5e; the masked sum fuses)
            chosen = jnp.sum(jnp.where(
                idx[..., None] == jnp.arange(self.n_routed), score[:, None],
                0.0), axis=-1)
            # renormalised over all the selected, held here or not
            gate = self.scale * chosen / jnp.sum(chosen, axis=-1,
                                                 keepdims=True)
            e_local = jnp.where(
                (idx >= self.expert_offset)
                & (idx < self.expert_offset + E),
                idx - self.expert_offset, -1)
        y = routed_experts(xf, gate.astype(x.dtype), e_local, w_gate, w_up,
                           w_down, operand_dtype=self.operand_dtype)
        y = y + SwiGLU(F, name="shared")(xf)
        held = e_local >= 0
        load = jnp.sum(
            held[None] & (e_local[None] == jnp.arange(E)[:, None, None]),
            axis=(1, 2))
        stats = {"local": jnp.sum(held, axis=-1).reshape(x.shape[:-1]),
                 "max_load": jnp.max(load)}
        return y.reshape(x.shape), stats
