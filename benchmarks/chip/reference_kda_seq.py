"""The plain reference of the delta-rule sequence tower: forward,
next-item cross entropy, gradients and Adam in straightforward
``jax.numpy`` float32 at ``highest`` matmul precision. It imports
nothing of the program and takes nothing the program made: its weights
come from ``weights_kda_seq.py`` and its batches from the generator.

Every layer is ``h + mixer(rms_norm(h) * w)``:

- ``K``: Kimi Delta Attention. ``x = silu(conv(u W_x))`` for x in q, k,
  v, each with its own four taps (tap ``j`` reads position ``t - 3 +
  j``, zeros before the start, no bias); a head at a time over its
  ``head_dim`` features ``q_t = q'_t / sqrt(sum q'_t^2 + l2_eps) *
  head_dim^(-1/2)``, ``k_t = k'_t / sqrt(sum k'_t^2 + l2_eps)``; the
  log-decay ``g_t = -exp(A_log[head]) softplus((u f_a) f_b + dt_bias)``,
  a value a key channel; the step size ``beta_t = sigmoid(u b_proj)``,
  one a head; then **the recurrence position by position**, a sequential
  ``lax.scan`` over ``t`` with a state ``S`` of (head_dim, head_dim) a
  head, never the chunked form the program runs::

      S~  = Diag(exp(g_t)) S_{t-1}
      S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T        o_t = S_t^T q_t

  and ``y_t = rms_norm_head(o_t) o_norm * sigmoid((u g_a) g_b)``, the
  mixer's output ``y o_proj``. The recurrence's backward runs under
  ``jax.checkpoint`` over blocks of positions: the carried states alone
  are 2 MB a position a layer;
- ``L``: latent attention without a low-rank query and without
  positions: ``q = u W_q`` a head ``nope + rope`` wide; ``[c | k_r] = u
  W_kva``, ``c = rms(c) w``; a head's ``[k_nope | v]`` from ``c W_kvb``;
  the head's key is ``[k_nope | k_r]`` with ``k_r`` shared by all the
  heads, **nothing rotated**; the full scores of a block of queries at
  a time, ``softmax(q k^T / sqrt(nope + rope)) v``;
- ``D``, ``E``: ``reference_latent_seq``'s gated feed-forward and its
  held experts' part beside the shared expert, as they are (scores over
  all the routed experts, the top ``experts_per_token``, the held pairs
  summed one expert at a time);
- the item head and the cross entropy against item t+1
  (``reference_hybrid_seq.head_loss``).

It is computed layer by layer so that the published widths at 8192
positions fit one chip beside Adam's state: the forward keeps each
layer's input, the backward takes one layer's ``jax.vjp`` at a time and
hands its gradients straight to Adam.

``precision="fp8"`` is the control: every matrix product's operands
(the recurrence's q, k and v among them), and every cotangent that flows
back through one, rounded to float8_e4m3 under a per-tensor scale.
``fault="unchanged"`` returns its state unchanged after every step;
``half_batch`` is the caller's.
"""

import math

from reference import _fake_quant   # float8_e4m3 rounding, no DLRM in it
from reference_hybrid_seq import _block_of, _rms, _silu, head_loss
from reference_latent_seq import dense_ffn, experts
from weights_kda_seq import layer_leaves


def delta_rule(q, k, v, g, beta):
    """``o_t = S_t^T q_t`` of the recurrence above, one position at a
    time. ``q``, ``k``, ``g`` (batch, T, heads, key width), ``v``
    (batch, T, heads, value width), ``beta`` (batch, T, heads)."""
    import jax
    import jax.numpy as jnp

    bs, t, heads, dk = q.shape
    block = _block_of(t, 128)

    def one(state, at):
        q_t, k_t, v_t, g_t, b_t = at                # (bs, heads, ..)
        state = jnp.exp(g_t)[..., None] * state
        held = jnp.sum(state * k_t[..., None], axis=-2)
        state = state + (b_t[..., None, None] * k_t[..., None]
                         * (v_t - held)[..., None, :])
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    @jax.checkpoint
    def over_block(state, at):
        return jax.lax.scan(one, state, at)

    def blocks(x):      # (bs, T, ...) -> (T / block, block, bs, ...)
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((t // block, block) + x.shape[1:])

    _, o = jax.lax.scan(over_block,
                        jnp.zeros((bs, heads, dk, v.shape[-1]), q.dtype),
                        tuple(blocks(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def delta_attention(p, u, sz, qz):
    import jax
    import jax.numpy as jnp

    bs, t, _ = u.shape
    heads, hd, taps = sz["kda_heads"], sz["kda_head_dim"], sz["conv_kernel"]

    def conved(name):
        x = jnp.pad(jnp.dot(qz(u), qz(p[f"{name}_proj"])),
                    ((0, 0), (taps - 1, 0), (0, 0)))
        x = _silu(sum(p[f"{name}_conv"][j] * x[:, j:j + t]
                      for j in range(taps)))
        return x.reshape(bs, t, heads, hd)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                            + sz["kda_l2_eps"])

    def low_rank(a, b):
        return jnp.dot(qz(jnp.dot(qz(u), qz(p[a]))), qz(p[b]))

    q = unit(conved("q")) / math.sqrt(hd)
    k, v = unit(conved("k")), conved("v")
    step = jax.nn.softplus(low_rank("f_a", "f_b") + p["dt_bias"])
    g = -jnp.exp(p["A_log"])[:, None] * step.reshape(bs, t, heads, hd)
    beta = 1.0 / (1.0 + jnp.exp(-jnp.dot(qz(u), qz(p["b_proj"]))))
    o = delta_rule(qz(q), qz(k), qz(v), g, beta)
    gate = 1.0 / (1.0 + jnp.exp(-low_rank("g_a", "g_b")))
    y = (_rms(o, sz["eps"]) * p["o_norm"]).reshape(bs, t, heads * hd) * gate
    return jnp.dot(qz(y), qz(p["o_proj"]))


def latent_attention(p, u, sz, qz):
    import jax
    import jax.numpy as jnp

    bs, t, _ = u.shape
    heads, nope, rope, vd = (sz["heads"], sz["nope_dim"], sz["rope_dim"],
                             sz["v_dim"])
    q = jnp.dot(qz(u), qz(p["q_proj"])).reshape(bs, t, heads, nope + rope)
    kva = jnp.dot(qz(u), qz(p["kv_a"]))
    c_kv = _rms(kva[..., :sz["kv_rank"]], sz["eps"]) * p["kv_norm"]
    kv = jnp.dot(qz(c_kv), qz(p["kv_b"])).reshape(bs, t, heads, nope + vd)
    shared = jnp.broadcast_to(kva[..., None, sz["kv_rank"]:],
                              (bs, t, heads, rope))
    k = jnp.concatenate([kv[..., :nope], shared], -1)
    q, k, v = (y.transpose(0, 2, 1, 3) for y in (q, k, kv[..., nope:]))
    block = _block_of(t, 512)
    key_at = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        q_blk, first = args                     # (bs, heads, block, d)
        s = (jnp.einsum("bhqd,bhkd->bhqk", qz(q_blk), qz(k))
             / math.sqrt(nope + rope))
        seen = key_at[None, :] <= (first + jnp.arange(block))[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        return jnp.einsum("bhqk,bhkd->bhqd", qz(w), qz(v))

    q_blocks = jnp.moveaxis(
        q.reshape(bs, heads, t // block, block, nope + rope), 2, 0)
    out = jax.lax.map(one, (q_blocks, jnp.arange(0, t, block)))
    out = jnp.moveaxis(out, 0, 2).reshape(bs, heads, t, vd)
    out = out.transpose(0, 2, 1, 3).reshape(bs, t, heads * vd)
    return jnp.dot(qz(out), qz(p["o_proj"]))


MIXERS = {"K": delta_attention, "L": latent_attention, "D": dense_ffn,
          "E": experts}


def layer(kind, p, h, sz, qz):
    return h + MIXERS[kind](p, _rms(h, sz["eps"]) * p["norm"], sz, qz)


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
