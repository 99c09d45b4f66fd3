"""Sizes and seeded weights of the selected-attention sequence tower
(grouped-query attention over the keys a learned indexer selects, beside
softmax-routed gated experts without a shared expert, an item table and
an item head), for the program's side and the plain reference alike.
Every leaf is a pure function of (seed, leaf index, element index), by
``weights_hybrid_seq.py``'s generator and its kinds, and ``zero``.

``sizes_of(config, override)`` reads a configuration with the published
keys of the ``KeyeVL2`` family's language model (the Qwen3-MoE body's,
and ``sa_config``) into plain names; ``override`` (a cell's rehearsal
``tower``) replaces any of them. Every block is ``SE``.

``S``'s leaves: ``q_proj`` (hidden, heads x head_dim), ``k_proj``,
``v_proj`` (hidden, kv_heads x head_dim), ``kernel``; ``q_norm``,
``k_norm`` (head_dim,), ``one``; ``o_proj`` (heads x head_dim, hidden),
``out``; the indexer's ``index_q`` (hidden, index_heads x index_dim),
``index_k`` (hidden, index_dim), ``index_w`` (hidden, index_heads),
``kernel``; ``index_k_scale`` (index_dim,), ``one``; ``index_k_bias``
(index_dim,), ``zero``. ``E``'s: ``router`` (hidden, routed),
``kernel``; ``w1`` (held, hidden, 2 x width), gate and up as one,
``kernel``; ``w2`` (held, width, hidden), ``out``; no shared expert.
"""

import numpy as np

import weights_hybrid_seq
from weights_hybrid_seq import seed_key  # noqa: F401


def sizes_of(config, override=None):
    """The tower's sizes under the plain names the placement, the
    weights, the reference and the costs share."""
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer has one key for all its heads")
    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1:
        raise ValueError("every block of this family feeds forward by "
                         "experts")
    sizes = {
        "pattern": "SE" * config["num_hidden_layers"],
        "hidden": config["hidden_size"],
        "vocab": config["vocab_size"],
        "eps": config["rms_norm_eps"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "rope_theta": config["rope_theta"],
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"],
        "index_rope_dim": config["indexer_rope_dim"],
        "topk": sa["topk"],
        "index_tile": sa["q_chunk_size"],
        "index_loss_weight": config["index_loss_weight"],
        "experts_routed": config["published"]["num_experts"],
        "experts_held": list(config["experts_held"]),
        "experts_per_token": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
    }
    if len(sizes["experts_held"]) != config["num_experts"]:
        raise ValueError("experts_held and num_experts disagree")
    if not config["norm_topk_prob"]:
        raise ValueError("this family's chosen weights are renormalised")
    sizes.update(override or {})
    return sizes


def layer_leaves(kind, sz):
    """[(param, shape, kind)] of one layer's mixer."""
    hidden = sz["hidden"]
    if kind == "S":
        q, kv = (n * sz["head_dim"] for n in (sz["heads"], sz["kv_heads"]))
        ih, idim = sz["index_heads"], sz["index_dim"]
        return [("q_proj", (hidden, q), "kernel"),
                ("k_proj", (hidden, kv), "kernel"),
                ("v_proj", (hidden, kv), "kernel"),
                ("q_norm", (sz["head_dim"],), "one"),
                ("k_norm", (sz["head_dim"],), "one"),
                ("o_proj", (q, hidden), "out"),
                ("index_q", (hidden, ih * idim), "kernel"),
                ("index_k", (hidden, idim), "kernel"),
                ("index_k_scale", (idim,), "one"),
                ("index_k_bias", (idim,), "zero"),
                ("index_w", (hidden, ih), "kernel")]
    if kind == "E":
        held, width = len(sz["experts_held"]), sz["expert_width"]
        return [("router", (hidden, sz["experts_routed"]), "kernel"),
                ("w1", (held, hidden, 2 * width), "kernel"),
                ("w2", (held, width, hidden), "out")]
    raise ValueError(f"unknown layer kind {kind!r}")


# the leaves only the alignment loss teaches
INDEXER = ("index_q", "index_k", "index_k_scale", "index_k_bias", "index_w")


def leaf_specs(sz):
    """[(name, shape, kind)] in the fixed order that numbers the leaves."""
    hidden = sz["hidden"]
    specs = [("table", (sz["vocab"], hidden), "table")]
    for i, kind in enumerate(sz["pattern"]):
        specs.append((f"L{i}.norm", (hidden,), "one"))
        specs += [(f"L{i}.{p}", shape, k)
                  for p, shape, k in layer_leaves(kind, sz)]
    specs.append(("final_norm", (hidden,), "one"))
    specs.append(("head", (hidden, sz["vocab"]), "kernel"))
    return specs


def parameters(sz):
    return sum(int(np.prod(shape)) for _, shape, _ in leaf_specs(sz))


def gen_leaf(key, index, shape, kind, sz):
    """One leaf, traced inside whatever jitted function calls it."""
    if kind == "zero":
        import jax.numpy as jnp

        return jnp.zeros(shape, jnp.float32)
    return weights_hybrid_seq.gen_leaf(key, index, shape, kind, sz)


def make(seed, sz, shardings=None):
    """{name: array}, one jitted call; ``shardings`` {name: sharding}."""
    import jax

    specs = leaf_specs(sz)

    def build(key):
        return {name: gen_leaf(key, i, shape, kind, sz)
                for i, (name, shape, kind) in enumerate(specs)}

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))
