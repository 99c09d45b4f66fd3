"""Sizes and seeded weights of the latent-attention sequence tower (latent
attention with decoupled rotary keys, a gated dense feed-forward, gated
sparse experts beside a shared expert, one multi-token-prediction
module, an item table and an item head), for the program's side and the
plain reference alike. Every leaf is a pure function of (seed, leaf
index, element index), by ``weights_hybrid_seq.py``'s generator and its
kinds (``table``, ``kernel``, ``out``, ``one``).

``sizes_of(config, override)`` reads a configuration with the published
keys of the ``glm4_moe_lite`` family into plain names; ``override`` (a
cell's rehearsal ``tower``) replaces any of them.

Layers, one letter each: ``L`` latent attention, ``D`` the gated dense
feed-forward, ``E`` the gated expert layer. A published block is two of
them (``LD`` for the ``first_k_dense_replace`` leading blocks, ``LE``
after). An expert's gate and up matrices are one leaf ``[gate | up]``,
as are the shared expert's and the dense feed-forward's.

Leaf names: ``table``, ``L<i>.norm`` and ``L<i>.<param>`` for layer
``i`` of the pattern, ``final_norm``, ``head``, and for the prediction
module ``mtp.embed_norm``, ``mtp.hidden_norm``, ``mtp.merge`` (2 hidden,
hidden; the embedding half first), ``mtp.L<j>.*`` for its block and
``mtp.head_norm`` (its block is ``mtp_pattern``, one more block like the
tower's last). The module has no head of its own: it reads ``head``.
"""

import numpy as np

from weights_hybrid_seq import gen_leaf, seed_key  # noqa: F401


def sizes_of(config, override=None):
    """The tower's sizes under the plain names the placement, the
    weights, the reference and the costs share."""
    blocks = config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    sizes = {
        "pattern": "LD" * dense + "LE" * (blocks - dense),
        "mtp_pattern": "LE",
        "mtp_depth": config["num_nextn_predict_layers"],
        "mtp_weight": config["mtp_loss_weight"],
        "hidden": config["hidden_size"],
        "vocab": config["vocab_size"],
        "eps": config["rms_norm_eps"],
        "heads": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"],
        "kv_rank": config["kv_lora_rank"],
        "nope_dim": config["qk_nope_head_dim"],
        "rope_dim": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"],
        "rope_theta": config["rope_theta"],
        "dense_width": config["intermediate_size"],
        "experts_routed": config["published"]["n_routed_experts"],
        "experts_held": list(config["experts_held"]),
        "experts_per_token": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": (config["n_shared_experts"]
                         * config["moe_intermediate_size"]),
        "routed_scaling": config["routed_scaling_factor"],
    }
    if len(sizes["experts_held"]) != config["n_routed_experts"]:
        raise ValueError("experts_held and n_routed_experts disagree")
    sizes.update(override or {})
    return sizes


def layer_leaves(kind, sz):
    """[(param, shape, kind)] of one layer's mixer."""
    hidden = sz["hidden"]
    if kind == "L":
        heads, nope, rope = sz["heads"], sz["nope_dim"], sz["rope_dim"]
        return [("q_a", (hidden, sz["q_rank"]), "kernel"),
                ("q_norm", (sz["q_rank"],), "one"),
                ("q_b", (sz["q_rank"], heads * (nope + rope)), "kernel"),
                ("kv_a", (hidden, sz["kv_rank"] + rope), "kernel"),
                ("kv_norm", (sz["kv_rank"],), "one"),
                ("kv_b", (sz["kv_rank"], heads * (nope + sz["v_dim"])),
                 "kernel"),
                ("o_proj", (heads * sz["v_dim"], hidden), "out")]
    if kind == "D":
        return [("gate_up", (hidden, 2 * sz["dense_width"]), "kernel"),
                ("down", (sz["dense_width"], hidden), "out")]
    if kind == "E":
        held, width = len(sz["experts_held"]), sz["expert_width"]
        return [("router", (hidden, sz["experts_routed"]), "kernel"),
                ("w1", (held, hidden, 2 * width), "kernel"),
                ("w2", (held, width, hidden), "out"),
                ("shared_w1", (hidden, 2 * sz["shared_width"]), "kernel"),
                ("shared_w2", (sz["shared_width"], hidden), "out")]
    raise ValueError(f"unknown layer kind {kind!r}")


def block_specs(prefix, pattern, sz):
    """The leaves of the layers of ``pattern`` under ``prefix``."""
    specs = []
    for i, kind in enumerate(pattern):
        specs.append((f"{prefix}L{i}.norm", (sz["hidden"],), "one"))
        specs += [(f"{prefix}L{i}.{p}", shape, k)
                  for p, shape, k in layer_leaves(kind, sz)]
    return specs


def leaf_specs(sz):
    """[(name, shape, kind)] in the fixed order that numbers the leaves."""
    hidden = sz["hidden"]
    specs = [("table", (sz["vocab"], hidden), "table")]
    specs += block_specs("", sz["pattern"], sz)
    specs.append(("final_norm", (hidden,), "one"))
    specs.append(("head", (hidden, sz["vocab"]), "kernel"))
    if sz["mtp_depth"]:
        specs += [("mtp.embed_norm", (hidden,), "one"),
                  ("mtp.hidden_norm", (hidden,), "one"),
                  ("mtp.merge", (2 * hidden, hidden), "kernel")]
        specs += block_specs("mtp.", sz["mtp_pattern"], sz)
        specs.append(("mtp.head_norm", (hidden,), "one"))
    return specs


def parameters(sz):
    return sum(int(np.prod(shape)) for _, shape, _ in leaf_specs(sz))


def make(seed, sz, shardings=None):
    """{name: array}, one jitted call; ``shardings`` {name: sharding}."""
    import jax

    specs = leaf_specs(sz)

    def build(key):
        return {name: gen_leaf(key, i, shape, kind, sz)
                for i, (name, shape, kind) in enumerate(specs)}

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))
