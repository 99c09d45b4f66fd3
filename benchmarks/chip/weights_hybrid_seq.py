"""Sizes and seeded weights of the hybrid sequence tower (state-space
mixers, sparse experts beside a shared expert, grouped-query attention,
an item table and an item head), for the program's side and the plain
reference alike. Neither takes a weight from the other: every leaf is a
pure function of (seed, leaf index, element index), by the murmur3
finaliser ``weights.py`` fills its tables with.

``sizes_of(config, override)`` reads a configuration with the published
keys of the ``nemotron_h`` family into the plain names both sides use;
``override`` (a cell's rehearsal ``tower``) replaces any of them, which
is how a CPU rehearsal runs the same code at widths a CPU can hold.

Leaf names: ``table`` (vocab, hidden), ``L<i>.norm`` and ``L<i>.<param>``
for layer ``i`` of the pattern, ``final_norm``, ``head`` (hidden, vocab).
Kinds and scales, chosen so that activations stay O(1) through the stack
and no router score ties:

- ``table``: uniform of variance 1;
- ``kernel``: uniform of variance 1/fan_in, an expert leaf's fan_in being
  its matrices'; ``out``: the same over the number of layers, for the
  projections that write to the residual stream (the published
  ``rescale_prenorm_residual``);
- ``conv``: uniform [-1/2, 1/2) (kernel 4), weight and bias;
- ``one``: 1 (norm weights, ``D``);
- ``a_log``: log of uniform [1, 16); ``dt_bias``: softplus's inverse of
  exp(uniform [log time_step_min, log time_step_max)), floored at
  ``time_step_floor``: the published ranges.
"""

import numpy as np


def sizes_of(config, override=None):
    """The tower's sizes under the plain names the placement, the
    weights, the reference and the costs share."""
    depth = config["num_hidden_layers"]
    sizes = {
        "pattern": config["hybrid_override_pattern"][:depth],
        "hidden": config["hidden_size"],
        "vocab": config["vocab_size"],
        "eps": config["layer_norm_epsilon"],
        "ssm_heads": config["mamba_num_heads"],
        "ssm_head_dim": config["mamba_head_dim"],
        "ssm_groups": config["n_groups"],
        "ssm_state": config["ssm_state_size"],
        "conv_kernel": config["conv_kernel"],
        "chunk": config["chunk_size"],
        "experts_routed": config["published"]["n_routed_experts"],
        "experts_held": list(config["experts_held"]),
        "experts_per_token": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["moe_shared_expert_intermediate_size"],
        "routed_scaling": config["routed_scaling_factor"],
        "attn_heads": config["num_attention_heads"],
        "attn_kv_heads": config["num_key_value_heads"],
        "attn_head_dim": config["head_dim"],
        "dt_limits": [config["time_step_min"], config["time_step_max"],
                      config["time_step_floor"]],
    }
    if len(sizes["experts_held"]) != config["n_routed_experts"]:
        raise ValueError("experts_held and n_routed_experts disagree")
    sizes.update(override or {})
    return sizes


def layer_leaves(kind, sz):
    """[(param, shape, kind)] of one layer's mixer."""
    hidden = sz["hidden"]
    if kind == "M":
        inner = sz["ssm_heads"] * sz["ssm_head_dim"]
        conv = inner + 2 * sz["ssm_groups"] * sz["ssm_state"]
        heads = sz["ssm_heads"]
        return [("in_proj", (hidden, inner + conv + heads), "kernel"),
                ("conv_w", (sz["conv_kernel"], conv), "conv"),
                ("conv_b", (conv,), "conv"),
                ("dt_bias", (heads,), "dt_bias"),
                ("A_log", (heads,), "a_log"),
                ("D", (heads,), "one"),
                ("norm_w", (inner,), "one"),
                ("out_proj", (inner, hidden), "out")]
    if kind == "E":
        held, width = len(sz["experts_held"]), sz["expert_width"]
        return [("router", (hidden, sz["experts_routed"]), "kernel"),
                ("w1", (held, hidden, width), "kernel"),
                ("w2", (held, width, hidden), "out"),
                ("shared_w1", (hidden, sz["shared_width"]), "kernel"),
                ("shared_w2", (sz["shared_width"], hidden), "out")]
    if kind == "*":
        q = sz["attn_heads"] * sz["attn_head_dim"]
        kv = sz["attn_kv_heads"] * sz["attn_head_dim"]
        return [("q_proj", (hidden, q), "kernel"),
                ("k_proj", (hidden, kv), "kernel"),
                ("v_proj", (hidden, kv), "kernel"),
                ("o_proj", (q, hidden), "out")]
    raise ValueError(f"unknown layer kind {kind!r}")


def leaf_specs(sz):
    """[(name, shape, kind)] in the fixed order that numbers the leaves."""
    specs = [("table", (sz["vocab"], sz["hidden"]), "table")]
    for i, kind in enumerate(sz["pattern"]):
        specs.append((f"L{i}.norm", (sz["hidden"],), "one"))
        specs += [(f"L{i}.{p}", shape, k)
                  for p, shape, k in layer_leaves(kind, sz)]
    specs.append(("final_norm", (sz["hidden"],), "one"))
    specs.append(("head", (sz["hidden"], sz["vocab"]), "kernel"))
    return specs


def parameters(sz):
    return sum(int(np.prod(shape)) for _, shape, _ in leaf_specs(sz))


def seed_key(seed):
    import jax

    return jax.random.key(int(seed) % 2147483647)


def _uniform(key, index, shape):
    """[0, 1) float32 by a hash of the element's flat index and a word
    drawn from (key, leaf index)."""
    import jax
    import jax.numpy as jnp

    u32 = jnp.uint32
    word = jax.random.bits(jax.random.fold_in(key, index), (), u32)
    x = jax.lax.iota(u32, int(np.prod(shape))).reshape(shape)
    x = x * u32(0x9E3779B9) + word
    x = (x ^ (x >> 16)) * u32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * u32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return (x >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def gen_leaf(key, index, shape, kind, sz):
    """One leaf, traced inside whatever jitted function calls it."""
    import jax.numpy as jnp

    if kind == "one":
        return jnp.ones(shape, jnp.float32)
    u = _uniform(key, index, shape)
    centred = (u - 0.5) * np.float32(np.sqrt(12.0))     # variance 1
    if kind == "table":
        return centred
    if kind in ("kernel", "out"):
        var = 1.0 / shape[-2]
        if kind == "out":
            var /= len(sz["pattern"])
        return centred * np.float32(np.sqrt(var))
    if kind == "conv":
        return u - 0.5
    if kind == "a_log":
        return jnp.log(1.0 + 15.0 * u)
    if kind == "dt_bias":
        lo, hi, floor = sz["dt_limits"]
        dt = jnp.maximum(jnp.exp(np.log(lo) + u * (np.log(hi) - np.log(lo))),
                         floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown leaf kind {kind!r}")


def make(seed, sz, shardings=None):
    """{name: array}, one jitted call; ``shardings`` {name: sharding}."""
    import jax

    specs = leaf_specs(sz)

    def build(key):
        return {name: gen_leaf(key, i, shape, kind, sz)
                for i, (name, shape, kind) in enumerate(specs)}

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))
