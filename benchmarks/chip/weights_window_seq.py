"""Sizes and seeded weights of the sliding-window sequence tower
(grouped-query attention over a window of keys, three to one with full
attention under a YaRN rotary rule, beside softmax-routed gated experts
without a shared expert, an item table and an item head), for the
program's side and the plain reference alike. Every leaf is a pure
function of (seed, leaf index, element index), by
``weights_hybrid_seq.py``'s generator and its kinds.

``sizes_of(config, override)`` reads a configuration with the published
keys of the ``mellum`` family into plain names; ``override`` (a cell's
rehearsal ``tower``) replaces any of them. Layer ``i`` (of
``num_hidden_layers``) attends by ``W`` where ``layer_types`` says
``sliding_attention`` and by ``A`` where it says ``full_attention``, and
feeds forward by ``E`` (``mlp_layer_types`` ``sparse``): a published
layer is two letters.

``W`` and ``A`` have the same leaves: ``q_proj`` (hidden, heads x
head_dim), ``k_proj``, ``v_proj`` (hidden, kv_heads x head_dim),
``kernel``; ``o_proj`` (heads x head_dim, hidden), ``out``. ``E``'s:
``router`` (hidden, routed), ``kernel``; ``w1`` (held, hidden, 2 x
width), gate and up as one, ``kernel``; ``w2`` (held, width, hidden),
``out``; no shared expert.
"""

import math

import numpy as np

from weights_hybrid_seq import gen_leaf, seed_key  # noqa: F401

ATTENTION = {"sliding_attention": "W", "full_attention": "A"}


def pattern_of(config):
    """The layer letters of layers 0..``num_hidden_layers`` - 1."""
    depth = config["num_hidden_layers"]
    kinds, mlps = config["layer_types"], config["mlp_layer_types"]
    if len(kinds) < depth or len(mlps) < depth:
        raise ValueError("layer_types and mlp_layer_types name every layer")
    if any(m != "sparse" for m in mlps[:depth]):
        raise ValueError("every layer of this family feeds forward by experts")
    return "".join(ATTENTION[kind] + "E" for kind in kinds[:depth])


def sizes_of(config, override=None):
    """The tower's sizes under the plain names the placement, the
    weights, the reference and the costs share. ``yarn`` is the full
    layers' rule as published: factor, original positions, beta_fast,
    beta_slow, and the factor on cos and sin."""
    rope = config["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if (full["rope_type"], sliding["rope_type"]) != ("yarn", "default"):
        raise ValueError("full layers under YaRN, sliding ones plain")
    if full["rope_theta"] != sliding["rope_theta"]:
        raise ValueError("one rotary base for both kinds of layer")
    # the amplitude the rule implies (mscale 1, no mscale_all_dim) is the
    # one the config states
    if not math.isclose(full["attention_factor"],
                        0.1 * math.log(full["factor"]) + 1.0, rel_tol=1e-12):
        raise ValueError("attention_factor is not 0.1 ln(factor) + 1")
    sizes = {
        "pattern": pattern_of(config),
        "hidden": config["hidden_size"],
        "vocab": config["vocab_size"],
        "eps": config["rms_norm_eps"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "window": config["sliding_window"],
        "rope_theta": full["rope_theta"],
        "yarn": {"factor": full["factor"],
                 "original_positions": full[
                     "original_max_position_embeddings"],
                 "beta_fast": full["beta_fast"],
                 "beta_slow": full["beta_slow"],
                 "attention_factor": full["attention_factor"]},
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
    if kind in ("W", "A"):
        q, kv = (n * sz["head_dim"] for n in (sz["heads"], sz["kv_heads"]))
        return [("q_proj", (hidden, q), "kernel"),
                ("k_proj", (hidden, kv), "kernel"),
                ("v_proj", (hidden, kv), "kernel"),
                ("o_proj", (q, hidden), "out")]
    if kind == "E":
        held, width = len(sz["experts_held"]), sz["expert_width"]
        return [("router", (hidden, sz["experts_routed"]), "kernel"),
                ("w1", (held, hidden, 2 * width), "kernel"),
                ("w2", (held, width, hidden), "out")]
    raise ValueError(f"unknown layer kind {kind!r}")


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


def make(seed, sz, shardings=None):
    """{name: array}, one jitted call; ``shardings`` {name: sharding}."""
    import jax

    specs = leaf_specs(sz)

    def build(key):
        return {name: gen_leaf(key, i, shape, kind, sz)
                for i, (name, shape, kind) in enumerate(specs)}

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))
