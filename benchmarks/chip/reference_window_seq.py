"""The plain reference of the sliding-window sequence tower: forward,
next-item cross entropy, gradients and Adam in straightforward
``jax.numpy`` float32 at ``highest`` matmul precision. It imports nothing
of the program and takes nothing the program made: its weights come from
``weights_window_seq.py`` and its batches from the generator.

Every layer is ``h + mixer(rms_norm(h) * w)``, ``u`` the normed input:

- ``W`` and ``A``: ``q = u W_q``, ``k = u W_k``, ``v = u W_v``, no bias
  and no norm on a head; every query and key head rotated whole,
  rotate-half pairs (feature i with feature i + d/2): ``W`` by the plain
  angle ``t theta^(-2i/d)`` (``reference_latent_seq.rotate``), ``A`` by
  ``t`` times the YaRN frequencies written out below as Hugging Face's
  ``_compute_yarn_parameters`` computes them, cos and sin times the
  rule's ``attention_factor``. Scores ``q k^T / sqrt(head_dim)``, a
  key-value head serving ``heads / kv_heads`` query heads, a block of
  queries at a time: ``A`` against every key under the causal mask,
  ``W`` against the ``window`` keys before the block and the block's
  own, under ``t - window < s <= t``. The mixer returns
  ``concat_h(o) W_o``;
- ``E``: ``reference_sparse_seq.experts``: softmax scores over all the
  routed experts, the top ``experts_per_token`` renormalised, a loop
  over the held expert ids, each gated expert over every token under a
  dense mask of its routing weights; no shared expert; what the experts
  held elsewhere would add is left out;
- the item head and the cross entropy against item t+1
  (``reference_hybrid_seq.head_loss``).

It is computed layer by layer so that the published widths at 8192
positions fit one chip beside Adam's state: the forward keeps each
layer's input, the backward takes one layer's ``jax.vjp`` at a time and
hands its gradients straight to Adam.

``precision="fp8"`` is the control: every matrix product's operands, and
every cotangent that flows back through one, rounded to float8_e4m3
under a per-tensor scale. ``fault="unchanged"`` returns its state
unchanged after every step; ``half_batch`` is the caller's.
"""

import math

import numpy as np

from reference import _fake_quant   # float8_e4m3 rounding, no DLRM in it
from reference_hybrid_seq import _block_of, _rms, head_loss
from reference_latent_seq import rotate
from reference_sparse_seq import experts
from weights_window_seq import layer_leaves


def yarn_frequencies(dim, theta, yarn):
    """The ``dim // 2`` rotary frequencies of a YaRN rule, float64, as
    Hugging Face's ``_compute_yarn_parameters`` gives ``inv_freq``: the
    pairs that turn more than ``beta_fast`` times over the original
    positions keep ``theta^(-2i/dim)``, those that turn less than
    ``beta_slow`` times have it divided by ``factor``, a linear ramp
    between (floor and ceiling of the two pair indices, clamped)."""
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos, 1.0 / (yarn["factor"] * pos)

    def correction(rotations):
        return (dim * math.log(yarn["original_positions"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    kept = 1.0 - ramp
    return interpolation * (1.0 - kept) + extrapolation * kept


def turn(x, kind, sz):
    """``x`` (batch, T, heads, d) rotated as layer ``kind`` rotates."""
    import jax.numpy as jnp

    if kind == "W":
        return rotate(x, sz["rope_theta"])
    t, half = x.shape[1], x.shape[-1] // 2
    freq = jnp.asarray(yarn_frequencies(x.shape[-1], sz["rope_theta"],
                                        sz["yarn"]), jnp.float32)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    factor = sz["yarn"]["attention_factor"]
    cos, sin = factor * jnp.cos(angle), factor * jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def attention(kind, p, u, sz, qz):
    """``W`` or ``A``: the mixer's output."""
    import jax
    import jax.numpy as jnp

    bs, t, _ = u.shape
    heads, kv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]

    def split(y, n, rotated):   # (bs, t, n * hd) -> (bs, n, t, hd)
        y = y.reshape(bs, t, n, hd)
        if rotated:
            y = turn(y, kind, sz)
        return y.transpose(0, 2, 1, 3)

    q = split(jnp.dot(qz(u), qz(p["q_proj"])), heads, True)
    k = jnp.repeat(split(jnp.dot(qz(u), qz(p["k_proj"])), kv, True),
                   heads // kv, axis=1)
    v = jnp.repeat(split(jnp.dot(qz(u), qz(p["v_proj"])), kv, False),
                   heads // kv, axis=1)
    block = _block_of(t, 512)
    # the keys a block of queries reads: all of them in an `A` layer, the
    # `before` keys ahead of the block and its own in a `W` layer (the
    # keys are padded at the front, so that every block reads as many)
    window = sz["window"] if kind == "W" else t
    before = min(window, t) if kind == "W" else 0
    k_pad, v_pad = (jnp.pad(x, ((0, 0), (0, 0), (before, 0), (0, 0)))
                    for x in (k, v))
    span = t if kind == "A" else block + before

    @jax.checkpoint
    def one(args):
        q_blk, first = args                     # (bs, heads, block, hd)
        start = 0 if kind == "A" else first
        k_blk, v_blk = (jax.lax.dynamic_slice_in_dim(x, start, span, axis=2)
                        for x in (k_pad, v_pad))
        key_at = start - before + jnp.arange(span)
        query = first + jnp.arange(block)
        seen = ((key_at[None, :] <= query[:, None])
                & (key_at[None, :] > query[:, None] - window)
                & (key_at[None, :] >= 0))
        s = (jnp.einsum("bhqd,bhkd->bhqk", qz(q_blk), qz(k_blk))
             / math.sqrt(hd))
        s = jnp.where(seen, s, -jnp.inf)
        a = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        a = a / jnp.sum(a, axis=-1, keepdims=True)
        return jnp.einsum("bhqk,bhkd->bhqd", qz(a), qz(v_blk))

    q_blocks = jnp.moveaxis(q.reshape(bs, heads, t // block, block, hd), 2, 0)
    out = jax.lax.map(one, (q_blocks, jnp.arange(0, t, block)))
    out = jnp.moveaxis(out, 0, 2).reshape(bs, heads, t, hd)
    out = out.transpose(0, 2, 1, 3).reshape(bs, t, heads * hd)
    return jnp.dot(qz(out), qz(p["o_proj"]))


def layer(kind, p, h, sz, qz):
    u = _rms(h, sz["eps"]) * p["norm"]
    if kind == "E":
        return h + experts(p, u, sz, qz)
    return h + attention(kind, p, u, sz, qz)


def first_steps(sz, opt, leaves, batches, precision="float32", fault=None):
    """``len(batches)`` plain training steps. ``leaves()`` makes the
    initial leaves {name: float32 array} from the seed, anew at every
    call; a batch is ``(rows, target)``, both (histories, T) int: the
    table row of each event and of the one that follows it. Returns
    ``losses``, ``grad_norm`` {leaf: norm of the first step's gradient}
    and ``change_norm`` {leaf: norm of the change over all the steps}.
    ``opt``: Adam's ``lr``, ``b1``, ``b2``, ``eps``."""
    import jax
    import jax.numpy as jnp

    qz = _fake_quant if precision == "fp8" else (lambda v: v)
    pattern = sz["pattern"]

    def fwd(kind):
        return jax.jit(lambda p, h: layer(kind, p, h, sz, qz))

    def bwd(kind):
        def f(p, h, dh):
            _, pull = jax.vjp(lambda p, h: layer(kind, p, h, sz, qz), p, h)
            return pull(dh)
        return jax.jit(f)

    @jax.jit
    def top(p, h, target):
        loss, (dp, dh) = jax.value_and_grad(
            lambda p, h: head_loss(p, h, target, sz, qz), argnums=(0, 1))(p, h)
        return loss, dp, dh

    @jax.jit
    def embed_grad(table, rows, dh):
        return jnp.zeros_like(table).at[rows].add(dh)

    @jax.jit
    def adam(p, mu, nu, g, step):
        b1, b2 = opt["b1"], opt["b2"]
        mu = b1 * mu + (1.0 - b1) * g
        nu = b2 * nu + (1.0 - b2) * g * g
        mu_hat = mu / (1.0 - b1 ** step)
        nu_hat = nu / (1.0 - b2 ** step)
        return (p - opt["lr"] * mu_hat / (jnp.sqrt(nu_hat) + opt["eps"]),
                mu, nu, jnp.linalg.norm(g))

    with jax.default_matmul_precision("highest"):
        fwds = {k: fwd(k) for k in set(pattern)}
        bwds = {k: bwd(k) for k in set(pattern)}
        params = dict(leaves())
        mu = {n: jnp.zeros_like(v) for n, v in params.items()}
        nu = {n: jnp.zeros_like(v) for n, v in params.items()}
        losses, grad_norm = [], {}

        def update(name, g, k):
            new, m, v, norm = adam(params[name], mu[name], nu[name], g,
                                   jnp.float32(k))
            if k == 1:
                grad_norm[name] = float(norm)
            if fault != "unchanged":
                params[name], mu[name], nu[name] = new, m, v

        def of_layer(i):
            names = ["norm"] + [n for n, _, _ in
                                layer_leaves(pattern[i], sz)]
            return {n: params[f"L{i}.{n}"] for n in names}

        for k, (rows, target) in enumerate(batches, start=1):
            rows, target = jnp.asarray(rows), jnp.asarray(target)
            hs = [params["table"][rows]]
            for i, kind in enumerate(pattern):
                hs.append(fwds[kind](of_layer(i), hs[-1]))
            loss, dp, dh = top({n: params[n] for n in ("final_norm", "head")},
                               hs.pop(), target)
            losses.append(float(loss))
            for n, g in dp.items():
                update(n, g, k)
            for i in reversed(range(len(pattern))):
                dp, dh = bwds[pattern[i]](of_layer(i), hs.pop(), dh)
                for n, g in dp.items():
                    update(f"L{i}.{n}", g, k)
            update("table", embed_grad(params["table"], rows, dh), k)
        del mu, nu      # room for a second set of leaves
        change_norm = {n: float(jnp.linalg.norm(params[n] - v))
                       for n, v in leaves().items()}
    return {"losses": losses, "grad_norm": grad_norm,
            "change_norm": change_norm}
