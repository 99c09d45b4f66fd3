"""Operations and bytes that the hyper-connected latent-attention
sequence tower's algorithm needs, from the configuration's widths: the
same work whatever implements it. Read by ``mfu.<configuration>``,
``flash_roofline.<configuration>`` and ``grouped_roofline.
<configuration>`` only; ``hyper_least_seconds`` is what PERF.md sets the
``hyper_connection`` scope's measured time beside.

A multiply-accumulate is two operations; the backward pass costs twice
the forward; recomputation is not counted.
"""

import costs_latent_seq
from costs_hybrid_seq import _least
from costs_latent_seq import grouped_least_seconds  # noqa: F401
from weights_hyper_seq import sizes_of


def forward_macs_per_event(sz, length):
    """{part: multiply-accumulates of one forward pass, an item event}:
    ``costs_latent_seq``'s parts (attention's scores at the key width
    ``nope + rope`` and its values at ``v_dim``; the latent projections;
    the dense feed-forward; shared and routed experts, these at the rows
    they are expected to see; the head) and, for each sublayer's
    hyper-connection over n streams of C features:

    - ``hyper_project``: the maps' projection, n C x (n n + 2 n);
    - ``hyper_mix``: the read-out (n C), the stream map (n n C) and the
      write-back (n C).
    """
    macs = costs_latent_seq.forward_macs_per_event(sz, length)
    n, hidden, sublayers = sz["streams"], sz["hidden"], len(sz["pattern"])
    maps = n * n + 2 * n
    macs["hyper_project"] = sublayers * n * hidden * maps
    macs["hyper_mix"] = sublayers * maps * hidden
    return macs


def train_flops_per_event(config, length):
    """Forward and backward: 2 operations a MAC, backward twice forward."""
    macs = forward_macs_per_event(sizes_of(config), length)
    return 3 * 2 * sum(macs.values())


def flash_least_seconds(sz, length, histories, peaks):
    """Least time of one training step's causal attention proper in the
    latent attention layers, whatever calls an implementation splits it
    into and whatever it recomputes: the forward pass once (scores
    contract the key width dk = nope + rope, values emit the value width
    dv) and the backward pass once (the scores again, dS K and dS^T Q at
    dk; P^T dO and dO V^T at dv), each of histories x heads x length
    (length + 1) / 2 multiply-accumulates a unit of width: 4 dk + 3 dv in
    all. Bytes: q, k and their gradients at dk, v, o and theirs at dv, at
    every head, each once a pass, bfloat16."""
    heads, dk, dv = sz["heads"], sz["nope_dim"] + sz["rope_dim"], sz["v_dim"]
    square = histories * heads * length * (length + 1) / 2    # MACs a unit
    one = histories * heads * length * 2                      # bytes a unit
    forward = _least(square * (dk + dv), one * 2 * (dk + dv), peaks)
    backward = _least(square * (3 * dk + 2 * dv), one * 4 * (dk + dv), peaks)
    return sz["pattern"].count("L") * (forward + backward)


def hyper_least_seconds(sz, length, histories, peaks):
    """Least time of one training step's hyper-connections: memory-bound
    work, so the bytes over the HBM peak. A sublayer's stream state S is
    histories x length x n x C in bfloat16, a single stream's S / n.
    Forward: S read, S written, the read-out u written and the mixer's y
    read (2 S + 2 S / n). Backward: S read again, the cotangent of the
    new state read, that of the old written, y read, and the cotangents
    of y and u written and read (3 S + 3 S / n)."""
    n = sz["streams"]
    state = histories * length * n * sz["hidden"] * 2
    return (len(sz["pattern"]) * (5 * state + 5 * state / n)
            / peaks["hbm_bytes_per_s"])
