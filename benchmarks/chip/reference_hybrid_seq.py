"""The plain reference of the hybrid sequence tower: forward, next-item
cross entropy, gradients and Adam in straightforward ``jax.numpy``
float32 at ``highest`` matmul precision. It imports nothing of the
program and takes nothing the program made: its weights come from
``weights_hybrid_seq.py`` and its batches from the generator.

Every layer is ``h + mixer(rms_norm(h) * w)``:

- ``M``: the state-space recurrence position by position, a sequential
  ``lax.scan`` (``S_t = exp(A dt_t) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t``), not the chunked form the program runs;
- ``E``: routing over all the routed experts, then a loop (``lax.scan``)
  over the held expert ids, each over every token under a dense mask of
  its routing weights, and the shared expert; what the experts held elsewhere would add is left out,
  as in the program;
- ``*``: the full score matrix, a block of queries at a time;
- the item head and the cross entropy over the table's rows.

It is computed layer by layer so that the published widths at 8192
positions fit one chip beside Adam's state: the forward keeps each
layer's input, the backward takes one layer's ``jax.vjp`` at a time
(recomputing its forward) and hands its gradients straight to Adam, so
no more than one layer's gradients exist at once. The recurrence's
backward runs under ``jax.checkpoint`` over blocks of positions: the
carried states alone are 2 MB a position a layer.

``precision="fp8"`` is the control: the same mathematics with every
matrix product's operands, and every cotangent that flows back through
one, rounded to float8_e4m3 under a per-tensor scale, the step below the
bfloat16 the configuration states. ``fault="unchanged"`` returns its
state unchanged after every step; ``half_batch`` is the caller's (it
cuts the batch before it gets here).
"""

import math

from reference import _fake_quant   # float8_e4m3 rounding, no DLRM in it
from weights_hybrid_seq import layer_leaves


def _rms(x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def _block_of(t, most):
    """The largest divisor of ``t`` that is at most ``most``."""
    return max(d for d in range(1, min(t, most) + 1) if t % d == 0)


def recurrence(x, dt, a, b, c):
    """``y_t = S_t C_t`` of the recurrence above, one position at a
    time. ``x`` (batch, T, heads, head_dim), ``dt`` (batch, T, heads),
    ``a`` (heads,) negative, ``b`` and ``c`` (batch, T, groups, state);
    head ``h`` reads group ``h // (heads // groups)``."""
    import jax
    import jax.numpy as jnp

    bs, t, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    per = heads // groups
    block = _block_of(t, 128)

    def one(state, at):
        x_t, dt_t, b_t, c_t = at
        b_h = jnp.repeat(b_t, per, axis=1)          # (bs, heads, n)
        c_h = jnp.repeat(c_t, per, axis=1)
        decay = jnp.exp(dt_t * a)                   # (bs, heads)
        state = (decay[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return state, jnp.sum(state * c_h[:, :, None, :], axis=-1)

    @jax.checkpoint
    def over_block(state, at):
        return jax.lax.scan(one, state, at)

    def blocks(v):      # (bs, T, ...) -> (T / block, block, bs, ...)
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((t // block, block) + v.shape[1:])

    _, y = jax.lax.scan(over_block, jnp.zeros((bs, heads, p, n), x.dtype),
                        (blocks(x), blocks(dt), blocks(b), blocks(c)))
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)


def ssm_mixer(p, u, sz, qz):
    import jax
    import jax.numpy as jnp

    bs, t, _ = u.shape
    heads, hd = sz["ssm_heads"], sz["ssm_head_dim"]
    groups, n, kern = sz["ssm_groups"], sz["ssm_state"], sz["conv_kernel"]
    inner, gn = heads * hd, groups * n
    zxbcdt = jnp.dot(qz(u), qz(p["in_proj"]))
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * gn], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (kern - 1, 0), (0, 0)))
    xbc = _silu(sum(p["conv_w"][k] * padded[:, k:k + t]
                    for k in range(kern)) + p["conv_b"])
    x, b, c = jnp.split(xbc, [inner, inner + gn], axis=-1)
    x = x.reshape(bs, t, heads, hd)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(qz(x), dt, -jnp.exp(p["A_log"]),
                   qz(b.reshape(bs, t, groups, n)),
                   qz(c.reshape(bs, t, groups, n)))
    y = (y + p["D"][:, None] * x).reshape(bs, t, inner) * _silu(z)
    y = _rms(y.reshape(bs, t, groups, inner // groups),
             sz["eps"]).reshape(bs, t, inner) * p["norm_w"]
    return jnp.dot(qz(y), qz(p["out_proj"]))


def routing(u, router, sz):
    """(chosen (tokens, k) expert ids, weights (tokens, k)) over all the
    routed experts: top k of sigmoid scores (the balancing bias is zero),
    weights ``scaling * s_e / sum of the chosen s``."""
    import jax
    import jax.numpy as jnp

    scores = 1.0 / (1.0 + jnp.exp(-jnp.dot(u, router)))
    _, chosen = jax.lax.top_k(scores, sz["experts_per_token"])
    s = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, sz["routed_scaling"] * s / jnp.sum(s, -1, keepdims=True)


def experts(p, u, sz, qz, held=None):
    """The held experts' part and the shared expert. ``held`` (ids)
    defaults to the sizes'; ``p["w1"][i]`` is expert ``held[i]``'s."""
    import jax
    import jax.numpy as jnp

    held = sz["experts_held"] if held is None else held
    bs, t, hidden = u.shape
    tokens = u.reshape(bs * t, hidden)
    chosen, weight = routing(tokens, p["router"], sz)

    def one(out, expert):
        e, w1, w2 = expert
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        mid = jnp.square(jnp.maximum(jnp.dot(qz(tokens), qz(w1)), 0.0))
        return out + w_e[:, None] * jnp.dot(qz(mid), qz(w2)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(tokens),
                          (jnp.asarray(held), p["w1"], p["w2"]))
    return (out + shared_expert(p, tokens, qz)).reshape(bs, t, hidden)


def shared_expert(p, tokens, qz):
    import jax.numpy as jnp

    mid = jnp.square(jnp.maximum(jnp.dot(qz(tokens), qz(p["shared_w1"])),
                                 0.0))
    return jnp.dot(qz(mid), qz(p["shared_w2"]))


def attention(p, u, sz, qz):
    """Causal softmax(q k^T / sqrt(d)) v, the full scores of a block of
    queries at a time; no positional encoding."""
    import jax
    import jax.numpy as jnp

    bs, t, _ = u.shape
    heads, kv, hd = sz["attn_heads"], sz["attn_kv_heads"], sz["attn_head_dim"]

    def split(y, n):    # (bs, t, n * hd) -> (bs, n, t, hd)
        return y.reshape(bs, t, n, hd).transpose(0, 2, 1, 3)

    q = split(jnp.dot(qz(u), qz(p["q_proj"])), heads)
    k = jnp.repeat(split(jnp.dot(qz(u), qz(p["k_proj"])), kv),
                   heads // kv, axis=1)
    v = jnp.repeat(split(jnp.dot(qz(u), qz(p["v_proj"])), kv),
                   heads // kv, axis=1)
    block = _block_of(t, 512)
    key_at = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        q_blk, first = args                          # (bs, heads, block, hd)
        s = jnp.einsum("bhqd,bhkd->bhqk", qz(q_blk), qz(k)) / math.sqrt(hd)
        seen = key_at[None, :] <= (first + jnp.arange(block))[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        return jnp.einsum("bhqk,bhkd->bhqd", qz(w), qz(v))

    q_blocks = jnp.moveaxis(q.reshape(bs, heads, t // block, block, hd), 2, 0)
    out = jax.lax.map(one, (q_blocks, jnp.arange(0, t, block)))
    out = jnp.moveaxis(out, 0, 2).reshape(bs, heads, t, hd)
    out = out.transpose(0, 2, 1, 3).reshape(bs, t, heads * hd)
    return jnp.dot(qz(out), qz(p["o_proj"]))


MIXERS = {"M": ssm_mixer, "E": experts, "*": attention}


def layer(kind, p, h, sz, qz):
    return h + MIXERS[kind](p, _rms(h, sz["eps"]) * p["norm"], sz, qz)


def head_loss(p, h, target, sz, qz):
    """Mean, over the positions with a target (>= 0), of the cross
    entropy of the item head's logits against the next item's row."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(qz(_rms(h, sz["eps"]) * p["final_norm"]), qz(p["head"]))
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = target >= 0
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, target, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked * valid) / jnp.maximum(jnp.sum(valid), 1)


def first_steps(sz, opt, leaves, batches, precision="float32", fault=None):
    """``len(batches)`` plain training steps. ``leaves()`` makes the
    initial leaves {name: float32 array} from the seed, anew at every
    call (once to start from, once to measure the change against); a
    batch is ``(rows, target)``,
    both (histories, T) int: the table row of each event and of the one
    that follows it. Returns ``losses``, ``grad_norm`` {leaf: norm of
    the first step's gradient} and ``change_norm`` {leaf: norm of the
    change over all the steps}. ``opt``: Adam's ``lr``, ``b1``, ``b2``,
    ``eps``."""
    import jax
    import jax.numpy as jnp

    qz = _fake_quant if precision == "fp8" else (lambda v: v)
    pattern = sz["pattern"]

    def names_of(i):
        return ["norm"] + [n for n, _, _ in layer_leaves(pattern[i], sz)]

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
            return {n: params[f"L{i}.{n}"] for n in names_of(i)}

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
        change_norm = {n: float(jnp.linalg.norm(params[n] - v))
                       for n, v in leaves().items()}
    return {"losses": losses, "grad_norm": grad_norm,
            "change_norm": change_norm}

