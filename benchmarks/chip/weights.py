"""The benchmark's own weights: every leaf a pure function of (seed, leaf
index, shape), made on the device in one jitted call. The program under
test gets them in place of its own initial values, and the plain
reference makes the same leaves again from the seed, so neither takes a
weight from the other.

Leaf names: ``table.<t>`` (rows, dim) uniform [0, 0.01), by a hash of the
element's index (``table_rows_of``); ``bottom.<l>``
and ``top.<l>`` with ``.kernel`` (fan_in, fan_out) normal of std
1/sqrt(fan_in) truncated at two std, and ``.bias`` zero — the families
the repo's modules initialise with (flax ``uniform(0.01)``, lecun-normal).
"""

import numpy as np


def table_rows(config, max_ind_range=None, multiple_of=1):
    """Rows each table holds: min(cardinality, cap), rounded up to the
    mesh's model axis. cap 0 = no cap."""
    cap = config["max_ind_range"] if max_ind_range is None else max_ind_range
    rows = [min(c, cap) if cap else c for c in config["table_cardinalities"]]
    return [r + (-r) % multiple_of for r in rows]


def mlp_shapes(config):
    """[(name, fan_in, fan_out)] of the bottom then the top MLP."""
    dim, tables = config["embedding_dim"], len(config["table_cardinalities"])
    out, fan_in = [], config["num_dense"]
    for i, w in enumerate(config["bottom_mlp"]):
        out.append((f"bottom.{i}", fan_in, w))
        fan_in = w
    fields = tables + 1
    fan_in = dim + fields * (fields - 1) // 2
    for i, w in enumerate(config["top_mlp"]):
        out.append((f"top.{i}", fan_in, w))
        fan_in = w
    return out


def leaf_specs(config, rows):
    """[(name, shape, kind)] in the fixed order that numbers the leaves."""
    dim = config["embedding_dim"]
    specs = [(f"table.{t}", (r, dim), "table") for t, r in enumerate(rows)]
    for name, fan_in, fan_out in mlp_shapes(config):
        specs.append((f"{name}.kernel", (fan_in, fan_out), "kernel"))
        specs.append((f"{name}.bias", (fan_out,), "bias"))
    return specs


def seed_key(seed):
    import jax

    return jax.random.key(int(seed) % 2147483647)


def table_rows_of(key, index, rows, dim):
    """Rows ``rows`` (any integer array) of table leaf ``index``: uniform
    [0, 0.01) by a murmur3 finaliser over (row * dim + column) and a seed
    word drawn from the key. A handful of integer operations an element,
    where ``jax.random.uniform`` took 18 s for a 3.8 GB model on a v5e
    (PERF.md, Findings), and any row can be made without the others."""
    import jax
    import jax.numpy as jnp

    u32 = jnp.uint32
    word = jax.random.bits(jax.random.fold_in(key, index), (), u32)
    col = jax.lax.broadcasted_iota(u32, rows.shape + (dim,), rows.ndim)
    x = rows.astype(u32)[..., None] * u32(dim) + col
    x = x * u32(0x9E3779B9) + word
    x = (x ^ (x >> 16)) * u32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * u32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return (x >> 8).astype(jnp.float32) * jnp.float32(0.01 / (1 << 24))


def gen_leaf(key, index, shape, kind):
    """One leaf, traced inside whatever jitted function calls it."""
    import jax
    import jax.numpy as jnp

    if kind == "table":
        rows = jax.lax.iota(jnp.uint32, shape[0])
        return table_rows_of(key, index, rows, shape[1])
    k = jax.random.fold_in(key, index)
    if kind == "kernel":
        std = 1.0 / np.sqrt(shape[0])
        return std * jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                                 jnp.float32)
    return jnp.zeros(shape, jnp.float32)


def make(seed, specs, shardings=None):
    """{name: array}, one jitted call; ``shardings`` {name: sharding}."""
    import jax

    def build(key):
        return {name: gen_leaf(key, i, shape, kind)
                for i, (name, shape, kind) in enumerate(specs)}

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


def hashed_rows(seed, table, ids0, dim):
    """(len(ids0), dim) float32 uniform [0, 0.01): the row of id ``i`` of a
    table too large to hold anywhere, a pure function of (seed, table, i,
    column) by splitmix64 mixing, so any row can be made alone. Used where
    rows live on parameter servers and only the compared steps' rows are
    put there by the benchmark."""
    u64 = np.uint64
    ids = np.asarray(ids0, np.uint64)[:, None]
    col = np.arange(dim, dtype=np.uint64)[None, :]
    with np.errstate(over="ignore"):
        x = (ids * u64(0x9E3779B97F4A7C15)
             + (col + u64(1)) * u64(0xD6E8FEB86659FD93)
             + u64((int(seed) * 1000003 + int(table) + 1)
                   % (1 << 63)) * u64(0xBF58476D1CE4E5B9))
        x = (x ^ (x >> u64(30))) * u64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> u64(27))) * u64(0x94D049BB133111EB)
        x = x ^ (x >> u64(31))
    return ((x >> u64(40)).astype(np.float64) / float(1 << 24)
            * 0.01).astype(np.float32)
