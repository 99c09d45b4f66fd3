"""Sizes and seeded weights of the hyper-connected latent-attention
sequence tower (``hc_mult`` residual streams mixed around every sublayer,
latent attention whose keys are wider than its values under a YaRN rotary
rule, a gated dense feed-forward, gated sparse experts beside a shared
expert, an item table and an item head), for the program's side and the
plain reference alike. Every leaf is a pure function of (seed, leaf
index, element index), by ``weights_hybrid_seq.py``'s generator and its
kinds.

``sizes_of(config, override)`` reads a configuration with the published
keys of the ``xing4_0`` family: ``weights_latent_seq.sizes_of``'s names
(the family shares its attention's and its experts' keys) and, beside
them, ``streams`` (``hc_mult``), ``sinkhorn_iters``, ``hyper_eps``,
``hyper_clamp`` and ``rope_scaling`` (the published record, whole).
``override`` (a cell's rehearsal ``tower``) replaces any of them.

Layers as ``weights_latent_seq.py`` has them (``L``, ``D``, ``E``; a
published block is two), each with the leaves of its hyper-connection
before its mixer's, n = ``streams``:

- ``L<i>.norm`` (hidden,): the mixer's input norm, ``one``;
- for each map ``pre`` (width n), ``post`` (n) and ``res`` (n n,
  row-major), three leaves of its own (the first sublayer's ``pre`` and
  ``res`` have no gradient but rounding, its streams being identical,
  and the comparison leaves such leaves out of the change gaps):
  ``L<i>.hyper_<map>_phi`` (n hidden, width), its part of the maps'
  projection, kind ``kernel``: variance 1 / (n hidden), so that the
  normed projection is of order one; ``L<i>.hyper_<map>_bias`` (width,),
  kind ``table``, uniform of variance 1: biases of order one;
  ``L<i>.hyper_<map>_scale`` (1,), ``one``.

With scales of one and a projection of order one the input-dependent
part of every map is as large as its bias: a map left static, streams
summed before the last block, or a Sinkhorn loop cut short moves the
loss and the gradients by more than any limit allows.
"""

import numpy as np

import weights_latent_seq as latent
from weights_hybrid_seq import gen_leaf, seed_key  # noqa: F401


def sizes_of(config, override=None):
    """The tower's sizes under the plain names the placement, the
    weights, the reference and the costs share."""
    sizes = latent.sizes_of({**config, "mtp_loss_weight": 0.0})
    sizes.update(
        streams=config["hc_mult"],
        sinkhorn_iters=config["hc_sinkhorn_iters"],
        hyper_eps=config["hc_eps"],
        hyper_clamp=[config["mhc_h_res_clamp_min"],
                     config["mhc_h_res_clamp_max"]],
        rope_scaling=dict(config["rope_scaling"]))
    sizes.update(override or {})
    return sizes


def hyper_leaves(sz):
    """[(param, shape, kind)] of one sublayer's hyper-connection."""
    n, out = sz["streams"], []
    for name, width in (("pre", n), ("post", n), ("res", n * n)):
        out += [(f"hyper_{name}_phi", (n * sz["hidden"], width), "kernel"),
                (f"hyper_{name}_bias", (width,), "table"),
                (f"hyper_{name}_scale", (1,), "one")]
    return out


def leaf_specs(sz):
    """[(name, shape, kind)] in the fixed order that numbers the leaves."""
    hidden = sz["hidden"]
    specs = [("table", (sz["vocab"], hidden), "table")]
    for i, kind in enumerate(sz["pattern"]):
        specs.append((f"L{i}.norm", (hidden,), "one"))
        specs += [(f"L{i}.{p}", shape, k) for p, shape, k in
                  hyper_leaves(sz) + latent.layer_leaves(kind, sz)]
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
