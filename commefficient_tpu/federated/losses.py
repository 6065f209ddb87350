"""Workload loss callbacks matching the worker contract.

``compute_loss(params, model_state, batch, rng, train) ->
(loss_sum, metric_sums, count, new_model_state)`` with sums over valid
(mask=1) examples.

CV head parity: cross-entropy + accuracy (reference cv_train.py:32-72);
the mixup variant exists in the reference but is dead code
(cv_train.py:74-80), so it is not reproduced.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax


def _cast_tree(tree, dtype):
    """Cast float32 leaves to the compute dtype (ints/keys untouched)."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if x.dtype == jnp.float32 else x, tree)


def _f32_tree(tree):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def make_cv_losses(model, has_batch_stats: bool = False,
                   compute_dtype: Optional[Any] = None):
    """Returns (compute_loss_train, compute_loss_val) for an image classifier
    flax module called as ``model.apply(vars, x, train=...)``.

    ``compute_dtype=jnp.bfloat16`` runs the forward/backward in bf16 on the
    MXU (TPU mixed precision, ``--bf16``): params and inputs are cast going
    in, logits come back to f32 before the softmax/CE, gradients flow back
    through the casts and emerge f32 — master weights, compression, and all
    server math stay f32. BatchNorm running stats are re-cast to f32 so the
    carried model_state keeps a stable dtype across rounds.
    """

    def _apply(params, model_state, x, train):
        if compute_dtype is not None:
            params = _cast_tree(params, compute_dtype)
            x = x.astype(compute_dtype)
        variables = {"params": params}
        if has_batch_stats:
            variables["batch_stats"] = model_state
            if train:
                logits, updates = model.apply(variables, x, train=True,
                                              mutable=["batch_stats"])
                return logits, _f32_tree(updates["batch_stats"])
            logits = model.apply(variables, x, train=False)
            return logits, model_state
        logits = model.apply(variables, x, train=train)
        return logits, model_state

    def compute(params, model_state, batch, rng, train):
        x = batch["inputs"]
        y = batch["targets"]
        mask = batch["mask"]
        logits, new_state = _apply(params, model_state, x, train)
        logits = logits.astype(jnp.float32)
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits, y.astype(jnp.int32))
        correct = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
        loss_sum = jnp.sum(losses * mask)
        acc_sum = jnp.sum(correct * mask)
        count = jnp.sum(mask)
        return loss_sum, (acc_sum,), count, new_state

    return compute, compute


def _mc_ce_acc(mc_logits, mc_labels):
    """Multiple-choice CE + accuracy over the candidate axis (shared by the
    dense and pipeline-parallel GPT-2 loss paths)."""
    logp = jax.nn.log_softmax(mc_logits, axis=-1)
    ce = -jnp.take_along_axis(logp, mc_labels[..., None], axis=-1)[..., 0]
    acc = (jnp.argmax(mc_logits, axis=-1) == mc_labels).astype(jnp.float32)
    return ce, acc


def make_gpt2_losses(model, lm_coef: float = 1.0, mc_coef: float = 1.0,
                     seq_axis: str | None = None,
                     compute_dtype: Optional[Any] = None,
                     moe_aux_coef: float = 0.0):
    """GPT-2 double-heads losses (reference gpt2_train.py:55-99).

    Train: ``lm_coef·lm_loss + mc_coef·mc_loss`` per example; no extra
    metrics (the reference returns a bare (loss,) tuple). Val: (nll, mc
    accuracy); perplexity is exp(mean nll) computed by the harness
    (reference gpt2_train.py:253). Deviation: per-example token-mean nll
    averaged over examples, where the reference means over all non-ignored
    tokens of the batch — identical when sequences have equal valid-token
    counts, and the per-example form is what masked client-weighted
    aggregation needs.

    ``seq_axis``: sequence-parallel mode — logits/labels carry only the
    local slice of the sequence (sharded over that mesh axis), the batch
    must provide pre-shifted labels under ``"lm_labels_shifted"`` (the
    shift crosses shard boundaries, so it happens host-side in the
    collate), and per-example token sums/counts are psum'ed over the axis
    so the loss value is replicated across seq shards.

    ``moe_aux_coef``: adds ``coef · Σ_layers aux`` per example to the
    training loss, where each MoE layer's Switch load-balancing aux
    (parallel/moe.py) is collected from the model's sown ``moe_losses``.
    Training-only; the val metrics stay pure NLL/accuracy.
    """

    def _lm_nll_per_example(lm_logits, batch):
        if seq_axis is not None:
            logits = lm_logits
            labels = batch["lm_labels_shifted"]
        else:
            # shift: predict token t+1 from position t (gpt2_train.py:63-67)
            logits = lm_logits[..., :-1, :]
            labels = batch["lm_labels"][..., 1:]
        valid = labels != -1
        safe = jnp.where(valid, labels, 0)
        # logsumexp − gathered logit, not log_softmax + gather: avoids
        # materializing a full (..., V) log-prob tensor (1.6 GB at the bench
        # geometry) — the reductions and the one-element gather are all the
        # loss needs. f32 accumulation regardless of the logits' dtype.
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(
            logits, safe[..., None], axis=-1)[..., 0].astype(jnp.float32)
        tok_nll = (lse - picked) * valid
        # sum over candidates & positions, normalize by valid token count
        nll_sum = tok_nll.sum(axis=(-2, -1))
        n_valid = valid.sum(axis=(-2, -1))
        if seq_axis is not None:
            # _psum_repct, not lax.psum: the replicated loss's cotangent is
            # identical on every seq shard, so the true VJP of this
            # reduction is the identity. A plain psum's transpose under
            # shard_map is another psum — measured doubling EVERY gradient
            # of the seq-parallel round (each shard's grad came out
            # nsq x its local-token contribution, breaking the worker's
            # "psum the shard grads at scale 1" contract,
            # federated/rounds.py).
            from commefficient_tpu.ops.collectives import psum_repct

            nll_sum = psum_repct(nll_sum, seq_axis)
            n_valid = jax.lax.psum(n_valid, seq_axis)  # int count: nondiff
        return nll_sum / jnp.maximum(n_valid, 1)

    def compute_train(params, model_state, batch, rng, train):
        if seq_axis is not None:
            # distinct dropout masks per seq shard (the shard's activations
            # are different positions of the same sequences)
            rng = jax.random.fold_in(rng, jax.lax.axis_index(seq_axis))
        if compute_dtype is not None:
            params = _cast_tree(params, compute_dtype)
        apply_kwargs = dict(
            token_type_ids=batch["token_type_ids"],
            mc_token_ids=batch["mc_token_ids"], train=train,
            rngs={"dropout": rng} if train else None)
        aux_total = 0.0
        if moe_aux_coef:
            (lm_logits, mc_logits), sown = model.apply(
                {"params": params}, batch["input_ids"],
                mutable=["moe_losses"], **apply_kwargs)
            leaves = jax.tree_util.tree_leaves(sown.get("moe_losses", {}))
            # mean over MoE layers (each layer sows one per-token-mean aux).
            # DELIBERATE DEVIATION from the Switch paper, which SUMS the
            # per-layer auxes (each weighted by alpha = 0.01): the mean
            # keeps the total aux magnitude depth-independent, so the
            # effective per-layer coefficient is moe_aux_coef / n_moe_layers
            # — weaker than Switch's for any model with > 1 MoE layer;
            # retune the coefficient accordingly rather than assuming
            # published values transfer
            if leaves:
                aux_total = sum(jnp.sum(jnp.asarray(leaf))
                                for leaf in leaves) / len(leaves)
        else:
            lm_logits, mc_logits = model.apply(
                {"params": params}, batch["input_ids"], **apply_kwargs)
        # lm_logits stay in compute dtype; the nll reductions accumulate
        # in f32 internally (see _lm_nll_per_example)
        mc_logits = mc_logits.astype(jnp.float32)
        lm_nll = _lm_nll_per_example(lm_logits, batch)
        mc_ce, _ = _mc_ce_acc(mc_logits, batch["mc_labels"])
        mask = batch["mask"]
        loss_sum = jnp.sum((lm_coef * lm_nll + mc_coef * mc_ce) * mask)
        if moe_aux_coef:
            # weighted by the client's valid-example count so the aux enters
            # the cross-client aggregation exactly like the per-example CE
            # terms (the round divides by the summed mask); with the
            # per-layer mean above the aux stays depth- and batch-size-
            # independent (per-layer weight = moe_aux_coef / n_moe_layers,
            # see the deviation note at the mean)
            loss_sum = loss_sum + moe_aux_coef * aux_total * jnp.sum(mask)
        return loss_sum, (), jnp.sum(mask), model_state

    def compute_val(params, model_state, batch, rng, train):
        if compute_dtype is not None:
            params = _cast_tree(params, compute_dtype)
        lm_logits, mc_logits = model.apply(
            {"params": params}, batch["input_ids"],
            token_type_ids=batch["token_type_ids"],
            mc_token_ids=batch["mc_token_ids"], train=False)
        # lm_logits stay in compute dtype; the nll reductions accumulate
        # in f32 internally (see _lm_nll_per_example)
        mc_logits = mc_logits.astype(jnp.float32)
        lm_nll = _lm_nll_per_example(lm_logits, batch)
        _, acc = _mc_ce_acc(mc_logits, batch["mc_labels"])
        mask = batch["mask"]
        return (jnp.sum(lm_nll * mask), (jnp.sum(acc * mask),),
                jnp.sum(mask), model_state)

    return compute_train, compute_val


def make_causal_lm_losses(model):
    """The losses of a decoder without a multiple-choice head or token-type
    embedding (models/joyai.py, models/laguna.py, models/ouro.py). An
    example's loss is a mean over its labelled positions (``lm_labels !=
    -1``, all candidates pooled), as in ``make_gpt2_losses``; the vocabulary
    is the model's slice. The batch is read here; what a position's loss is,
    and which metric sums the train path returns beside the loss
    (``metric_names``, of which ``metric_ratios`` names those the event log
    divides by another's sum), is the model's configuration's to say, in one
    of two forms (``model.cfg.reads_labels``):

    - the model returns ``(logits, counts)``: next-token cross-entropy, and
      ``cfg.metric_sums(counts, sequences)`` gives the metric sums a
      sequence;
    - the model takes the labels and returns per-position terms (so that it
      never holds more than one pass's logits: models/ouro.py):
      ``cfg.position_terms(*terms, train)`` gives a position's loss, whether
      its prediction was the label, and its counters, which are summed here
      over the labelled positions with no gradient.

    Train returns the metric sums, val (loss, next-token accuracy over the
    labelled positions).

    ``compute_train.over_clients`` takes a leading clients axis on the batch
    and returns per-client vectors: the round's fused client phase calls it
    in place of a ``vmap`` over clients, so that all clients' sequences of a
    microbatch are one batch axis (one token axis for an expert layer's
    routing)."""
    cfg = model.cfg

    def per_example(params, batch, train):
        ids = batch["input_ids"]
        lead, T = ids.shape[:-2], ids.shape[-1]            # (..., B), K, T

        def by_example(x):      # sum over an example's candidates/positions
            return x.reshape(lead + (-1,)).sum(axis=-1)

        if cfg.reads_labels:
            labels = batch["lm_labels"].reshape(-1, T)[:, 1:]
            valid = labels != -1
            tok_loss, hit, counters = cfg.position_terms(
                *model.apply({"params": params}, ids.reshape(-1, T), labels),
                train)
            n_valid = jnp.maximum(by_example(valid), 1)
            counts = tuple(jax.lax.stop_gradient(by_example(c * valid))
                           for c in counters)
            return (by_example(tok_loss * valid) / n_valid,
                    by_example(hit & valid) / n_valid, counts)
        logits, stats = model.apply({"params": params}, ids.reshape(-1, T))
        labels = batch["lm_labels"].reshape(-1, T)[:, 1:]
        valid = labels != -1
        lg = logits[:, :-1].astype(jnp.float32)
        picked = jnp.take_along_axis(
            lg, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
        tok_nll = (jax.nn.logsumexp(lg, axis=-1) - picked) * valid
        hit = (jnp.argmax(lg, axis=-1) == labels) & valid
        n_valid = jnp.maximum(by_example(valid), 1)
        counts = tuple(
            jax.lax.stop_gradient(by_example(c.astype(jnp.float32)))
            for c in cfg.metric_sums(stats, logits.shape[0]))
        return (by_example(tok_nll) / n_valid, by_example(hit) / n_valid,
                counts)

    def sums(params, batch, train):
        """(loss sum, metric sums, count) over the last (examples) axis."""
        loss, acc, counts = per_example(params, batch, train)
        mask = batch["mask"]
        extra = (tuple(jnp.sum(c, axis=-1) for c in counts) if train
                 else (jnp.sum(acc * mask, axis=-1),))
        return jnp.sum(loss * mask, axis=-1), extra, jnp.sum(mask, axis=-1)

    def compute_train(params, model_state, batch, rng, train):
        return sums(params, batch, True) + (model_state,)

    def over_clients(params, model_states, batch, rngs):
        return sums(params, batch, True) + (model_states,)

    def compute_val(params, model_state, batch, rng, train):
        return sums(params, batch, False) + (model_state,)

    compute_train.over_clients = over_clients
    compute_train.metric_names = cfg.metric_names
    compute_train.metric_ratios = cfg.metric_ratios
    return compute_train, compute_val
