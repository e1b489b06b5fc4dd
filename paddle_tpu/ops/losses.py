"""Loss functions.

Parity with the reference's cost layers (reference:
gserver/layers/CostLayer.cpp — multi-class CE, soft-label CE, squared error,
rank cost, lambda rank, multi-binary-label CE, huber, sum cost) and Fluid
loss ops (reference: paddle/operators/cross_entropy_op.cc,
softmax_with_cross_entropy_op.cc, smooth_l1_loss_op.cc,
margin_rank_loss_op.cc, hinge_loss_op.cc). All losses return per-example
values; reduce with weights via `reduce_loss`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.dtypes import at_least_f32


def reduce_loss(per_example, weights=None, reduction: str = "mean"):
    if weights is not None:
        per_example = per_example * weights
    if reduction == "mean":
        if weights is not None:
            return jnp.sum(per_example) / jnp.maximum(jnp.sum(weights), 1.0)
        return jnp.mean(per_example)
    if reduction == "sum":
        return jnp.sum(per_example)
    return per_example


def softmax_cross_entropy(logits, labels, *, label_smoothing: float = 0.0):
    """Integer-label softmax CE (reference: softmax_with_cross_entropy_op.cc,
    gserver MultiClassCrossEntropy). logits [..., C], labels [...] int."""
    num_classes = logits.shape[-1]
    log_p = jax.nn.log_softmax(at_least_f32(logits), axis=-1)
    onehot = jax.nn.one_hot(labels, num_classes, dtype=log_p.dtype)
    if label_smoothing > 0.0:
        onehot = onehot * (1.0 - label_smoothing) + label_smoothing / num_classes
    return -jnp.sum(onehot * log_p, axis=-1)


def soft_label_cross_entropy(logits, soft_labels):
    """Soft-label CE (reference: SoftBinaryClassCrossEntropy / soft_label path
    of cross_entropy_op.cc)."""
    log_p = jax.nn.log_softmax(at_least_f32(logits), axis=-1)
    return -jnp.sum(soft_labels * log_p, axis=-1)


def cross_entropy_with_probs(probs, labels, *, epsilon: float = 1e-8):
    """CE on already-softmaxed probabilities (reference: cross_entropy_op.cc
    takes probabilities, not logits)."""
    p = jnp.take_along_axis(probs, labels[..., None], axis=-1)[..., 0]
    return -jnp.log(p + epsilon)


def sigmoid_cross_entropy(logits, labels):
    """Element-wise binary CE from logits (reference:
    sigmoid_cross_entropy_with_logits_op.cc). Numerically stable form."""
    logits = at_least_f32(logits)
    labels = at_least_f32(labels)
    return jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))


def multi_binary_label_cross_entropy(logits, labels):
    """Multi-label binary CE summed over classes (reference:
    gserver MultiBinaryLabelCrossEntropy)."""
    return jnp.sum(sigmoid_cross_entropy(logits, labels), axis=-1)


def squared_error(pred, target):
    """Sum-of-squares cost (reference: gserver SumOfSquaresCostLayer).
    Per-example 0.5*||d||^2 (squared_l2_distance below is the Fluid-op
    variant without the 1/2)."""
    d = at_least_f32((pred - target))
    return 0.5 * jnp.sum(jnp.square(d), axis=tuple(range(1, d.ndim)))


mse = squared_error


def huber_regression(pred, target, delta: float = 1.0):
    """Huber regression loss (reference: gserver HuberRegressionLoss)."""
    d = jnp.abs(at_least_f32((pred - target)))
    quad = 0.5 * jnp.square(d)
    lin = delta * (d - 0.5 * delta)
    per_elem = jnp.where(d <= delta, quad, lin)
    return jnp.sum(per_elem, axis=tuple(range(1, per_elem.ndim)))


def huber_classification(pred, labels):
    """Huber loss for binary classification with labels {0,1}
    (reference: gserver HuberTwoClassification, modified_huber_loss_op.cc)."""
    y = 2.0 * at_least_f32(labels) - 1.0
    z = at_least_f32(pred).squeeze(-1) if pred.ndim > labels.ndim else at_least_f32(pred)
    a = y * z
    return jnp.where(a < -1.0, -4.0 * a, jnp.square(jnp.maximum(1.0 - a, 0.0)))


def smooth_l1(pred, target, sigma: float = 1.0):
    """Smooth-L1 (reference: operators/smooth_l1_loss_op.cc)."""
    sigma2 = sigma * sigma
    d = at_least_f32((pred - target))
    ad = jnp.abs(d)
    per_elem = jnp.where(ad < 1.0 / sigma2, 0.5 * sigma2 * jnp.square(d), ad - 0.5 / sigma2)
    return jnp.sum(per_elem, axis=tuple(range(1, per_elem.ndim)))


def hinge_loss(logits, labels):
    """Hinge loss with {0,1} labels (reference: operators/hinge_loss_op.cc)."""
    y = 2.0 * at_least_f32(labels) - 1.0
    return jnp.maximum(0.0, 1.0 - y * at_least_f32(logits))


def rank_cost(left, right, label):
    """Pairwise rank cost (reference: gserver RankingCost,
    operators/rank_loss_op.cc). label in [0,1]: P(left ranked above right)."""
    o = at_least_f32((left - right))
    return jax.nn.softplus(o) - label * o


def margin_rank_loss(left, right, label, margin: float = 0.0):
    """Margin rank loss (reference: operators/margin_rank_loss_op.cc).
    label in {-1, +1}."""
    return jnp.maximum(0.0, -label * (left - right) + margin)


def lambda_rank_segment(scores, relevance, *, ndcg_num: int = 5):
    """LambdaRank cost for ONE query list (reference: gserver LambdaCost).

    scores, relevance: [L]. Returns scalar pairwise lambda loss weighted by
    |delta NDCG|. Use vmap over padded query groups.
    """
    scores = at_least_f32(scores)
    rel = at_least_f32(relevance)
    gains = jnp.power(2.0, rel) - 1.0
    # ideal DCG over top ndcg_num
    sorted_gains = jnp.sort(gains)[::-1]
    discounts = 1.0 / jnp.log2(jnp.arange(
        sorted_gains.shape[0], dtype=jnp.int32) + 2.0)
    topk_mask = (jnp.arange(
        sorted_gains.shape[0], dtype=jnp.int32) < ndcg_num).astype(jnp.float32)
    ideal_dcg = jnp.sum(sorted_gains * discounts * topk_mask)
    inv_idcg = jnp.where(ideal_dcg > 0, 1.0 / jnp.maximum(ideal_dcg, 1e-12), 0.0)
    # current ranks by score (descending)
    order = jnp.argsort(-scores)
    ranks = jnp.empty_like(order).at[order].set(jnp.arange(
        scores.shape[0], dtype=jnp.int32))
    disc = 1.0 / jnp.log2(at_least_f32(ranks) + 2.0)
    sij = scores[:, None] - scores[None, :]
    delta_ndcg = jnp.abs((gains[:, None] - gains[None, :]) * (disc[:, None] - disc[None, :])) * inv_idcg
    higher = at_least_f32((rel[:, None] > rel[None, :]))
    pair_loss = jax.nn.softplus(-sij) * delta_ndcg * higher
    return jnp.sum(pair_loss)


def cos_sim(a, b, scale: float = 1.0, epsilon: float = 1e-8):
    """Cosine similarity (reference: function/CosSimOp.cpp, operators/cos_sim_op.cc)."""
    a32, b32 = at_least_f32(a), at_least_f32(b)
    dot = jnp.sum(a32 * b32, axis=-1)
    na = jnp.sqrt(jnp.sum(jnp.square(a32), axis=-1))
    nb = jnp.sqrt(jnp.sum(jnp.square(b32), axis=-1))
    return scale * dot / jnp.maximum(na * nb, epsilon)


# Fluid's op name for the same formula huber_classification implements
# (reference: operators/modified_huber_loss_op.cc == gserver
# HuberTwoClassification) — one implementation, two API names.
modified_huber_loss = huber_classification


def squared_l2_distance(x, y):
    """Row-wise squared L2 distance WITHOUT the 1/2 factor (reference:
    operators/squared_l2_distance_op.cc; squared_error above is the
    gserver SumOfSquaresCostLayer variant carrying the 1/2)."""
    return 2.0 * squared_error(x, y)


def l1_norm(x):
    """sum |x| (reference: operators/l1_norm_op.cc)."""
    return jnp.sum(jnp.abs(at_least_f32(x)))


def squared_l2_norm(x):
    """sum x^2 (reference: operators/squared_l2_norm_op.cc)."""
    return jnp.sum(jnp.square(at_least_f32(x)))


@jax.named_scope("fused_ce")
def chunked_lm_head_nll(hidden, kernel, targets, *, chunk: int = 2048,
                        bias=None):
    """Next-token NLL fused with the LM-head matmul, never holding the
    full [N, V] logits.

    The plain path (models/transformer.loss) computes
    `logits = h @ W` for all N = B*T positions, then logsumexp —
    at the flagship bench shape (B4 T8191 V32000) that is a 4.2 GiB
    f32 tensor written by the forward, saved as a backward residual,
    and swept twice more by the softmax VJP: pure HBM traffic on a
    bandwidth-bound chip. Here the positions are processed in
    `chunk`-row slices inside a `lax.scan` whose body is
    `jax.checkpoint`ed: the forward keeps only the per-position nll
    (N floats), and the backward recomputes each chunk's logits on the
    MXU right before consuming them — trading cheap recompute FLOPs
    for the dominant HBM bytes, the same exchange `jax.checkpoint`
    makes for block activations (reference analog: the reference
    fuses softmax into its CE op for the same reason,
    softmax_with_cross_entropy_op.cc — one pass instead of two; this
    takes it one step further by folding in the projection).

    hidden [B, T, D] (compute dtype), kernel [D, V], targets [B, T]
    int, bias optional [V] (the seq2seq decoder head carries one; the
    transformer LM head does not). Returns per-position nll [B, T]
    f32. Bit-compatibility with the unfused path is to
    matmul-accumulation order only (same ops, chunked lhs), so values
    match to ~1e-6 relative.
    """
    from paddle_tpu.ops import linalg

    b, t, d = hidden.shape
    n = b * t
    h = hidden.reshape(n, d)
    y = targets.reshape(n)
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, d), h.dtype)])
        y = jnp.concatenate([y, jnp.zeros((pad,), y.dtype)])
    h = h.reshape(n_chunks, chunk, d)
    y = y.reshape(n_chunks, chunk)

    @jax.checkpoint
    def body(carry, hy):
        hc, yc = hy
        logits = at_least_f32(linalg.matmul(hc, kernel))
        if bias is not None:
            logits = logits + at_least_f32(bias)[None, :]
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return carry, lse - gold

    _, nll = jax.lax.scan(body, None, (h, y))
    return nll.reshape(n_chunks * chunk)[:n].reshape(b, t)
