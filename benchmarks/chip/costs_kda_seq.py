"""Operations and bytes that the delta-rule sequence tower's algorithm
needs, from the configuration's widths: the same work whatever
implements it. Read by ``mfu.<configuration>``, ``flash_roofline.
<configuration>`` and ``grouped_roofline.<configuration>`` only;
``kda_least_seconds`` is what PERF.md sets the ``kda_scan`` scope's
measured time beside (and what a ``kda_roofline`` would be read against,
once the recurrence is a kernel with a trace group of its own).

A multiply-accumulate is two operations; the backward pass costs twice
the forward; recomputation is not counted.
"""

from costs_hybrid_seq import _least
from costs_hyper_seq import flash_least_seconds  # noqa: F401
from costs_latent_seq import grouped_least_seconds  # noqa: F401
from weights_kda_seq import sizes_of


def recurrence_macs_per_event(sz):
    """One Kimi Delta Attention layer's recurrence as the
    position-by-position form needs it, a head: what the decayed state
    holds under the key (key width x value width), the rank-one
    correction, and the read-out under the query; the decay itself is a
    multiplication a state entry and no multiply-accumulate. Less than
    the chunked form multiplies."""
    return 3 * sz["kda_heads"] * sz["kda_head_dim"] ** 2


def forward_macs_per_event(sz, length):
    """{part: multiply-accumulates of one forward pass, an item event}
    over a history of ``length`` events:

    - ``kda_project``: a ``K`` layer's projections (query, key, value,
      output), its two gates' bottlenecks, its step size and its three
      convolutions' taps;
    - ``kda_recurrence``: :func:`recurrence_macs_per_event`;
    - ``latent_project``: the four projections of the latent attention
      layer (query, key-value down and up, output);
    - ``latent_attention``: causal scores and values, (length + 1) / 2
      keys a query on average, heads x (nope + rope) and heads x v;
    - ``dense_ffn``, ``experts_routed`` (the router, and the held
      experts' three matrices at the rows they are expected to see,
      per_token x held / routed of an event), ``experts_shared``,
      ``head``: as ``costs_latent_seq`` counts them.
    """
    hidden, heads = sz["hidden"], sz["heads"]
    inner = sz["kda_heads"] * sz["kda_head_dim"]
    kda = (4 * hidden * inner + 2 * sz["kda_head_dim"] * (hidden + inner)
           + hidden * sz["kda_heads"] + 3 * sz["conv_kernel"] * inner)
    qk, vd = sz["nope_dim"] + sz["rope_dim"], sz["v_dim"]
    project = (hidden * heads * qk + hidden * (sz["kv_rank"] + sz["rope_dim"])
               + sz["kv_rank"] * heads * (sz["nope_dim"] + vd)
               + heads * vd * hidden)
    share = (sz["experts_per_token"] * len(sz["experts_held"])
             / sz["experts_routed"])
    routed = (hidden * sz["experts_routed"]
              + share * 3 * hidden * sz["expert_width"])
    n = {k: sz["pattern"].count(k) for k in "KLDE"}
    return {"kda_project": n["K"] * kda,
            "kda_recurrence": n["K"] * recurrence_macs_per_event(sz),
            "latent_project": n["L"] * project,
            "latent_attention": n["L"] * heads * (qk + vd) * (length + 1) / 2,
            "dense_ffn": n["D"] * 3 * hidden * sz["dense_width"],
            "experts_routed": n["E"] * routed,
            "experts_shared": n["E"] * 3 * hidden * sz["shared_width"],
            "head": hidden * sz["vocab"]}


def train_flops_per_event(config, length):
    """Forward and backward: 2 operations a MAC, backward twice forward."""
    macs = forward_macs_per_event(sizes_of(config), length)
    return 3 * 2 * sum(macs.values())


def kda_least_seconds(sz, length, histories, peaks):
    """Least time of one training step's recurrence in the ``K`` layers,
    whatever form an implementation gives it: the larger of its
    operations (forward once, backward twice that) over the bf16 peak
    and its bytes over the HBM peak. Bytes: ``q``, ``k``, ``v`` and
    ``o`` in bfloat16 and the log-decay ``g`` in float32, heads x
    head_dim a position each, and the step size ``beta`` in float32, a
    head; each and its gradient once."""
    heads, hd = sz["kda_heads"], sz["kda_head_dim"]
    events = histories * length
    macs = 3 * events * recurrence_macs_per_event(sz)
    nbytes = 2 * events * heads * (hd * (4 * 2 + 4) + 4)
    return sz["pattern"].count("K") * _least(macs, nbytes, peaks)
