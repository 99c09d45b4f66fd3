"""Operations and bytes that the selected-attention sequence tower's
algorithm needs, from the configuration's widths and the pairs its
indexer selects: the same work whatever implements it. Read by
``mfu.<configuration>``, ``flash_roofline.<configuration>`` and
``grouped_roofline.<configuration>`` only; ``index_least_seconds`` is
what PERF.md sets the ``index_scores`` scope's measured time beside (and
what an ``index_roofline`` would be read against, once the indexer is a
kernel with a trace group of its own).

A multiply-accumulate is two operations; the backward pass costs twice
the forward; recomputation is not counted. Attention is counted over the
selected pairs only: an implementation that walks every causal pair
under a mask earns nothing for the rest.
"""

from costs_hybrid_seq import _least
from costs_latent_seq import grouped_least_seconds  # noqa: F401
from weights_sparse_seq import sizes_of


def selected_pairs(length, topk):
    """(query, key) pairs one history's ``S`` layer selects: ``min(t +
    1, topk)`` for query ``t``."""
    short = min(length, topk)
    return short * (short + 1) // 2 + (length - short) * topk


def forward_macs_per_event(sz, length):
    """{part: multiply-accumulates of one forward pass, an item event}
    over a history of ``length`` events:

    - ``select_project``: an ``S`` layer's four attention projections;
    - ``index_project``: the indexer's three (queries, the one key, the
      heads' weights);
    - ``index_scores``: ``I`` over every causal pair, index_heads x
      index_dim a pair;
    - ``selected_attention``: scores and values over the selected pairs,
      heads x head_dim each;
    - ``experts_routed``: the router, and the held experts' three
      matrices at the rows they are expected to see, per_token x held /
      routed of an event; there is no shared expert;
    - ``head``.
    """
    hidden = sz["hidden"]
    q, kv = (n * sz["head_dim"] for n in (sz["heads"], sz["kv_heads"]))
    index = sz["index_heads"] * sz["index_dim"]
    share = (sz["experts_per_token"] * len(sz["experts_held"])
             / sz["experts_routed"])
    n = {k: sz["pattern"].count(k) for k in "SE"}
    return {"select_project": n["S"] * hidden * (2 * q + 2 * kv),
            "index_project": n["S"] * hidden * (
                index + sz["index_dim"] + sz["index_heads"]),
            "index_scores": n["S"] * index * (length + 1) / 2,
            "selected_attention": n["S"] * 2 * q * selected_pairs(
                length, sz["topk"]) / length,
            "experts_routed": n["E"] * (
                hidden * sz["experts_routed"]
                + share * 3 * hidden * sz["expert_width"]),
            "head": hidden * sz["vocab"]}


def train_flops_per_event(config, length):
    """Forward and backward: 2 operations a MAC, backward twice forward."""
    macs = forward_macs_per_event(sizes_of(config), length)
    return 3 * 2 * sum(macs.values())


def selected_least_seconds(sz, length, pairs_by_layer, peaks):
    """Least time of one training step's attention proper in the ``S``
    layers, given the pairs each selected (one number a layer, summed
    over the step's histories): the forward pass once (scores, values)
    and the backward pass once (the scores again, P^T dO, dO V^T, dS K,
    dS^T Q), seven products of heads x head_dim multiply-accumulates a
    selected pair. Bytes: q, o and their gradients at the query heads'
    count, k, v and theirs at the key-value heads', each once a pass,
    bfloat16; at these sizes the operations bind."""
    heads, kv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    one = length * hd * 2                                   # bytes a head
    forward_bytes = one * (2 * heads + 2 * kv)
    return sum(_least(2 * pairs * heads * hd, forward_bytes, peaks)
               + _least(5 * pairs * heads * hd, 2 * forward_bytes, peaks)
               for pairs in pairs_by_layer)


def index_least_seconds(sz, length, histories, peaks):
    """Least time of one training step's index scores in the ``S``
    layers: ``I`` forward and the two products that carry its gradient
    back to the indexer's queries and to its key, each index_heads x
    index_dim multiply-accumulates a causal pair. Bytes: the indexer's
    queries (bfloat16), its weights (float32) and their gradients, once
    each; the key is small."""
    ih, idim = sz["index_heads"], sz["index_dim"]
    pairs = histories * length * (length + 1) / 2
    nbytes = histories * length * ih * (2 * idim * 2 + 2 * 4)
    return sz["pattern"].count("S") * _least(3 * pairs * ih * idim, nbytes,
                                             peaks)
