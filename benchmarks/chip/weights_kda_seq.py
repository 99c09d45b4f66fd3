"""Sizes and seeded weights of the delta-rule sequence tower (Kimi Delta
Attention layers beside latent attention without a low-rank query and
without positions, a gated dense feed-forward, gated sparse experts
beside a shared expert, an item table and an item head), for the
program's side and the plain reference alike. Every leaf is a pure
function of (seed, leaf index, element index), by
``weights_hybrid_seq.py``'s generator and its kinds.

``sizes_of(config, override)`` reads a configuration with the published
keys of the ``kimi_linear`` family into plain names; ``override`` (a
cell's rehearsal ``tower``) replaces any of them. The pattern is read
off the published layer lists: block ``b`` (1-based, up to
``num_hidden_layers``) mixes by ``K`` where ``linear_attn_config.
kda_layers`` names it and by ``L`` where ``full_attn_layers`` does, and
feeds forward by ``D`` up to ``first_k_dense_replace`` and by ``E``
after.

Layers, one letter each; a published block is two of them. ``K``'s
leaves (``inner`` = heads x head_dim):

- ``q_proj``, ``k_proj``, ``v_proj`` (hidden, inner), ``kernel``;
- ``q_conv``, ``k_conv``, ``v_conv`` (conv_kernel, inner), ``conv``: tap
  ``j`` reads position ``t - (conv_kernel - 1) + j``; no bias;
- ``f_a`` (hidden, head_dim), ``f_b`` (head_dim, inner): the decay's
  bottleneck, as wide as a head, ``kernel``; ``dt_bias`` (inner,),
  ``dt_bias``; ``A_log`` (heads,), ``a_log``: the published ranges of
  the state-space family;
- ``b_proj`` (hidden, heads): the step size, ``kernel``;
- ``g_a``, ``g_b``: the output gate's bottleneck, as ``f_a``, ``f_b``;
- ``o_norm`` (head_dim,), ``one``; ``o_proj`` (inner, hidden), ``out``.

``L`` without a low-rank query: ``q_proj`` (hidden, heads x (nope +
rope)), ``kv_a``, ``kv_norm``, ``kv_b``, ``o_proj`` as
``weights_latent_seq.py`` has them. ``D`` and ``E`` are that file's.
"""

import numpy as np

import weights_latent_seq as latent
from weights_hybrid_seq import gen_leaf, seed_key  # noqa: F401


def pattern_of(config):
    """The layer letters of blocks 1..``num_hidden_layers``."""
    lists = config["linear_attn_config"]
    letters = []
    for block in range(1, config["num_hidden_layers"] + 1):
        if block in lists["kda_layers"]:
            letters.append("K")
        elif block in lists["full_attn_layers"]:
            letters.append("L")
        else:
            raise ValueError(f"block {block} is in neither layer list")
        letters.append("D" if block <= config["first_k_dense_replace"]
                       else "E")
    return "".join(letters)


def sizes_of(config, override=None):
    """The tower's sizes under the plain names the placement, the
    weights, the reference and the costs share."""
    linear = config["linear_attn_config"]
    sizes = {
        "pattern": pattern_of(config),
        "hidden": config["hidden_size"],
        "vocab": config["vocab_size"],
        "eps": config["rms_norm_eps"],
        "kda_heads": linear["num_heads"],
        "kda_head_dim": linear["head_dim"],
        "conv_kernel": linear["short_conv_kernel_size"],
        "kda_chunk": config["kda_chunk"],
        "kda_l2_eps": config["kda_l2_eps"],
        "dt_limits": list(config["kda_dt_limits"]),
        "heads": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"],
        "kv_rank": config["kv_lora_rank"],
        "nope_dim": config["qk_nope_head_dim"],
        "rope_dim": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"],
        "positions": not config["mla_use_nope"],
        "dense_width": config["intermediate_size"],
        "experts_routed": config["published"]["num_experts"],
        "experts_held": list(config["experts_held"]),
        "experts_per_token": config["num_experts_per_token"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": (config["num_shared_experts"]
                         * config["moe_intermediate_size"]),
        "routed_scaling": config["routed_scaling_factor"],
    }
    if len(sizes["experts_held"]) != config["num_experts"]:
        raise ValueError("experts_held and num_experts disagree")
    if sizes["q_rank"] is not None or sizes["positions"]:
        raise ValueError("this family's latent attention has no low-rank "
                         "query and no positions")
    sizes.update(override or {})
    return sizes


def layer_leaves(kind, sz):
    """[(param, shape, kind)] of one layer's mixer."""
    hidden = sz["hidden"]
    if kind == "K":
        heads, hd = sz["kda_heads"], sz["kda_head_dim"]
        inner, taps = heads * hd, sz["conv_kernel"]
        return ([(f"{x}_proj", (hidden, inner), "kernel") for x in "qkv"]
                + [(f"{x}_conv", (taps, inner), "conv") for x in "qkv"]
                + [("f_a", (hidden, hd), "kernel"),
                   ("f_b", (hd, inner), "kernel"),
                   ("dt_bias", (inner,), "dt_bias"),
                   ("A_log", (heads,), "a_log"),
                   ("b_proj", (hidden, heads), "kernel"),
                   ("g_a", (hidden, hd), "kernel"),
                   ("g_b", (hd, inner), "kernel"),
                   ("o_norm", (hd,), "one"),
                   ("o_proj", (inner, hidden), "out")])
    if kind == "L":
        heads, nope, rope = sz["heads"], sz["nope_dim"], sz["rope_dim"]
        return [("q_proj", (hidden, heads * (nope + rope)), "kernel"),
                ("kv_a", (hidden, sz["kv_rank"] + rope), "kernel"),
                ("kv_norm", (sz["kv_rank"],), "one"),
                ("kv_b", (sz["kv_rank"], heads * (nope + sz["v_dim"])),
                 "kernel"),
                ("o_proj", (heads * sz["v_dim"], hidden), "out")]
    return latent.layer_leaves(kind, sz)


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
