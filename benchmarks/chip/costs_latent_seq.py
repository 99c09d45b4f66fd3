"""Operations and bytes that the latent-attention sequence tower's
algorithm needs, from the configuration's widths: the same work whatever
implements it. Read by ``mfu.<configuration>``, ``flash_roofline.
<configuration>`` and ``grouped_roofline.<configuration>`` only.

A multiply-accumulate is two operations; the backward pass costs twice
the forward; recomputation is not counted.
"""

from costs_hybrid_seq import _least
from weights_latent_seq import sizes_of


def blocks(sz):
    """Every layer letter the step runs: the tower's pattern and the
    prediction module's block."""
    return sz["pattern"] + sz["mtp_pattern"] * bool(sz["mtp_depth"])


def forward_macs_per_event(sz, length):
    """{part: multiply-accumulates of one forward pass, an item event}
    over a history of ``length`` events:

    - ``latent_project``: the five projections of a latent attention
      layer (query down and up, key-value down and up, output);
    - ``latent_attention``: causal scores and values, (length + 1) / 2
      keys a query on average, heads x (nope + rope) and heads x v;
    - ``dense_ffn``: the gated feed-forward's three matrices;
    - ``experts_routed``: the router, and the held experts' three
      matrices at the rows they are expected to see, per_token x held /
      routed of an event;
    - ``experts_shared``: the shared expert's three matrices;
    - ``heads``: the item head, once for the main path and once for the
      prediction module, and the module's merge (2 hidden x hidden).
    """
    hidden, heads = sz["hidden"], sz["heads"]
    qk, vd = sz["nope_dim"] + sz["rope_dim"], sz["v_dim"]
    project = (hidden * sz["q_rank"] + sz["q_rank"] * heads * qk
               + hidden * (sz["kv_rank"] + sz["rope_dim"])
               + sz["kv_rank"] * heads * (sz["nope_dim"] + vd)
               + heads * vd * hidden)
    attend = heads * (qk + vd) * (length + 1) / 2
    share = (sz["experts_per_token"] * len(sz["experts_held"])
             / sz["experts_routed"])
    routed = (hidden * sz["experts_routed"]
              + share * 3 * hidden * sz["expert_width"])
    n = {k: blocks(sz).count(k) for k in "LDE"}
    ahead = bool(sz["mtp_depth"])
    return {"latent_project": n["L"] * project,
            "latent_attention": n["L"] * attend,
            "dense_ffn": n["D"] * 3 * hidden * sz["dense_width"],
            "experts_routed": n["E"] * routed,
            "experts_shared": n["E"] * 3 * hidden * sz["shared_width"],
            "heads": ((1 + ahead) * hidden * sz["vocab"]
                      + ahead * 2 * hidden * hidden)}


def train_flops_per_event(config, length):
    """Forward and backward: 2 operations a MAC, backward twice forward."""
    macs = forward_macs_per_event(sizes_of(config), length)
    return 3 * 2 * sum(macs.values())


def flash_least_seconds(sz, length, histories, peaks):
    """Least time of one training step's causal attention proper in the
    latent attention layers (the module's included), whatever calls an
    implementation splits it into and whatever it recomputes: the
    forward pass once (2 products: scores, values) and the backward pass
    once (5: the scores again, P^T dO, dO V^T, dS K, dS^T Q), each of
    histories x heads x length (length + 1) / 2 x head width
    multiply-accumulates, where queries, keys and values are all
    ``nope_dim + rope_dim = v_dim`` wide. Bytes: q, k, v, o and their
    gradients at every head (the uncompressed form builds keys and values
    a head), each once a pass, bfloat16."""
    heads, hd = sz["heads"], sz["v_dim"]
    square = histories * heads * length * (length + 1) / 2 * hd   # MACs
    one = histories * heads * length * hd * 2                     # bytes
    forward = _least(2 * square, 4 * one, peaks)
    backward = _least(5 * square, 8 * one, peaks)
    return blocks(sz).count("L") * (forward + backward)


def grouped_least_seconds(sz, rows_by_layer, peaks):
    """Least time of one training step's grouped products over the held
    experts, given the rows routed to them in each expert layer (their
    sum over the held experts, one number a layer): a layer needs two
    products forward (rows x hidden x 2 width for gate and up as one,
    rows x width x hidden) and four backward (a gradient to the rows and
    one to the matrices, for each), once each whatever is recomputed.
    Bytes a product: the rows in and out and the held experts' matrices
    once, bfloat16."""
    hidden, width = sz["hidden"], sz["expert_width"]
    held = len(sz["experts_held"])

    def product(rows, k, n):
        return _least(rows * k * n,
                      2 * (rows * (k + n) + held * k * n), peaks)

    return sum(3 * (product(rows, hidden, 2 * width)
                    + product(rows, width, hidden))
               for rows in rows_by_layer)
