"""Operations and bytes that the hybrid sequence tower's algorithm needs,
from the configuration's widths: the same work whatever implements it.
Read by ``mfu.<configuration>`` and ``kernels_roofline`` only.

A multiply-accumulate is two operations; the backward pass costs twice
the forward; recomputation is not counted.
"""

from weights_hybrid_seq import sizes_of


def forward_macs_per_event(sz, length):
    """{part: multiply-accumulates of one forward pass, an item event}
    over a history of ``length`` events:

    - ``ssm``: the M layers' two projections and the recurrence as the
      position-by-position form needs it (the state fed and read, 2 x
      heads x head_dim x state a position), which is less than the
      chunked form multiplies;
    - ``experts_routed``: the router, and the held experts at the rows
      they are expected to see, per_token x held / routed of an event;
    - ``experts_shared``: the shared expert's two products;
    - ``attention``: the four projections and causal scores and values,
      (length + 1) / 2 keys a query on average;
    - ``head``: the item head.
    """
    hidden = sz["hidden"]
    inner = sz["ssm_heads"] * sz["ssm_head_dim"]
    conv = inner + 2 * sz["ssm_groups"] * sz["ssm_state"]
    ssm = (hidden * (inner + conv + sz["ssm_heads"]) + inner * hidden
           + 2 * inner * sz["ssm_state"])
    share = (sz["experts_per_token"] * len(sz["experts_held"])
             / sz["experts_routed"])
    routed = (hidden * sz["experts_routed"]
              + share * 2 * hidden * sz["expert_width"])
    shared = 2 * hidden * sz["shared_width"]
    q = sz["attn_heads"] * sz["attn_head_dim"]
    kv = sz["attn_kv_heads"] * sz["attn_head_dim"]
    attn = (hidden * (2 * q + 2 * kv) + 2 * q * (length + 1) / 2)
    n = {k: sz["pattern"].count(k) for k in "ME*"}
    return {"ssm": n["M"] * ssm, "experts_routed": n["E"] * routed,
            "experts_shared": n["E"] * shared, "attention": n["*"] * attn,
            "head": hidden * sz["vocab"]}


def train_flops_per_event(config, length):
    """Forward and backward: 2 operations a MAC, backward twice forward."""
    macs = forward_macs_per_event(sizes_of(config), length)
    return 3 * 2 * sum(macs.values())


def _least(macs, nbytes, peaks):
    """The larger of operations over the bf16 peak and bytes over the
    HBM peak."""
    return max(2 * macs / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def flash_least_seconds(sz, length, histories, peaks):
    """Least time of one training step's causal attention proper (what
    the flash kernel is there for), whatever calls an implementation
    splits it into and whatever it recomputes: the forward pass once (2
    products: scores, values) and the backward pass once (5: the scores
    again, since no algorithm that fits keeps histories x heads x length^2
    of them; P^T dO, dO V^T, dS K, dS^T Q), each of histories x heads x
    length (length + 1) / 2 x head_dim multiply-accumulates (causal: half
    of the score squares). Bytes: q, o and their gradients at the query
    heads' count, k, v and theirs at the key-value heads' count, each
    once a pass, bfloat16. Times the attention layers."""
    heads, kv, hd = sz["attn_heads"], sz["attn_kv_heads"], sz["attn_head_dim"]
    square = histories * heads * length * (length + 1) / 2 * hd   # MACs
    q_bytes = histories * heads * length * hd * 2
    kv_bytes = histories * kv * length * hd * 2
    forward = _least(2 * square, 2 * q_bytes + 2 * kv_bytes, peaks)
    backward = _least(5 * square, 4 * q_bytes + 4 * kv_bytes, peaks)
    return sz["pattern"].count("*") * (forward + backward)


def grouped_least_seconds(sz, rows_by_layer, peaks):
    """Least time of one training step's grouped products over the held
    experts, given the rows routed to them in each expert layer (their
    sum over the held experts, one number a layer): a layer needs the two
    products forward and four backward (a gradient to the rows and one to
    the matrices, for each product), once each whatever is recomputed,
    all of rows x hidden x width multiply-accumulates. Bytes a product:
    the rows in and out and the held experts' matrices once, bfloat16."""
    hidden, width = sz["hidden"], sz["expert_width"]
    held = len(sz["experts_held"])
    return sum(6 * _least(rows * hidden * width,
                          2 * (rows * (hidden + width)
                               + held * hidden * width), peaks)
               for rows in rows_by_layer)
