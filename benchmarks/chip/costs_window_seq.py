"""Operations and bytes that the sliding-window sequence tower's
algorithm needs, from the configuration's widths: the same work whatever
implements it. Read by ``mfu.<configuration>``, ``window_roofline.
<configuration>``, ``flash_roofline.<configuration>`` and
``grouped_roofline.<configuration>`` only.

A multiply-accumulate is two operations; the backward pass costs twice
the forward; recomputation is not counted. A ``W`` layer's attention is
counted over the band only (``min(t + 1, window)`` keys for query ``t``):
an implementation that walks every causal pair under a mask earns
nothing for the rest.
"""

from costs_hybrid_seq import _least
from costs_latent_seq import grouped_least_seconds  # noqa: F401
from weights_window_seq import sizes_of


def band_pairs(length, window):
    """(query, key) pairs of one history that a ``W`` layer attends:
    ``min(t + 1, window)`` for query ``t``."""
    short = min(length, window)
    return short * (short + 1) // 2 + (length - short) * window


def causal_pairs(length):
    return length * (length + 1) // 2


def forward_macs_per_event(sz, length):
    """{part: multiply-accumulates of one forward pass, an item event}
    over a history of ``length`` events:

    - ``attention_project``: the four projections of every ``W`` and
      ``A`` layer;
    - ``full_attention``: the ``A`` layers' scores and values over every
      causal pair, heads x head_dim each;
    - ``window_attention``: the ``W`` layers' over the band's pairs;
    - ``experts_routed``: the router, and the held experts' three
      matrices at the rows they are expected to see, per_token x held /
      routed of an event; there is no shared expert;
    - ``head``.
    """
    hidden = sz["hidden"]
    q, kv = (n * sz["head_dim"] for n in (sz["heads"], sz["kv_heads"]))
    share = (sz["experts_per_token"] * len(sz["experts_held"])
             / sz["experts_routed"])
    n = {k: sz["pattern"].count(k) for k in "WAE"}
    return {"attention_project": (n["W"] + n["A"]) * hidden * (
                2 * q + 2 * kv),
            "full_attention": n["A"] * 2 * q * causal_pairs(length) / length,
            "window_attention": n["W"] * 2 * q * band_pairs(
                length, sz["window"]) / length,
            "experts_routed": n["E"] * (
                hidden * sz["experts_routed"]
                + share * 3 * hidden * sz["expert_width"]),
            "head": hidden * sz["vocab"]}


def train_flops_per_event(config, length):
    """Forward and backward: 2 operations a MAC, backward twice forward."""
    macs = forward_macs_per_event(sizes_of(config), length)
    return 3 * 2 * sum(macs.values())


def _attention_least(sz, events, pairs, layers, peaks):
    """One training step's attention proper over ``pairs`` (query, key)
    pairs a layer at ``events`` positions, in ``layers`` layers: the
    forward pass once (scores, values) and the backward pass once (the
    scores again, P^T dO, dO V^T, dS K, dS^T Q), seven products of heads
    x head_dim multiply-accumulates a pair. Bytes: q, o and their
    gradients at the query heads' count, k, v and theirs at the
    key-value heads', each once a pass, bfloat16; at these sizes the
    operations bind."""
    heads, kv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    forward_bytes = events * hd * 2 * (2 * heads + 2 * kv)
    return layers * (_least(2 * pairs * heads * hd, forward_bytes, peaks)
                     + _least(5 * pairs * heads * hd, 2 * forward_bytes,
                              peaks))


def window_least_seconds(sz, length, histories, peaks):
    """Least time of one training step's attention in the ``W`` layers:
    seven products a pair of the band, whatever implements it."""
    return _attention_least(
        sz, length * histories,
        histories * band_pairs(length, sz["window"]),
        sz["pattern"].count("W"), peaks)


def flash_least_seconds(sz, length, histories, peaks):
    """Least time of one training step's attention in the ``A`` layers:
    seven products a causal pair."""
    return _attention_least(sz, length * histories,
                            histories * causal_pairs(length),
                            sz["pattern"].count("A"), peaks)
