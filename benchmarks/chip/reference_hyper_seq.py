"""The plain reference of the hyper-connected latent-attention sequence
tower: forward, cross entropy, gradients and Adam in straightforward
``jax.numpy`` float32 at ``highest`` matmul precision. It imports
nothing of the program and takes nothing the program made: its weights
come from ``weights_hyper_seq.py`` and its batches from the generator.

n = ``streams`` residual streams, C = ``hidden``. The state is X of
shape (T, n, C) a history.

- **Expansion**: ``X_0[t, j] = e_t`` for every stream j, the table's row
  copied n times. **Contraction** after the last sublayer: ``h_t = sum_j
  X[t, j]``, then the final norm, the item head and the cross entropy
  against item t+1 (``reference_hybrid_seq.head_loss``).
- **A sublayer** F (``L``, ``D`` or ``E``), with its own phi (nC, n n +
  2 n), bias b and scales (a_pre, a_post, a_res), each held as three
  leaves ``hyper_<pre|post|res>_<phi|bias|scale>``, phi's columns and
  b's entries in the order ``[pre | post | res]``:

  - ``z_t`` = X[t] flattened to nC; ``m_t = (z_t phi) (mean(z_t^2) +
    hyper_eps)^(-1/2)``, with no learned gain;
  - ``pre_t = sigmoid(a_pre m_t[0:n] + b[0:n])``; ``post_t = 2
    sigmoid(a_post m_t[n:2n] + b[n:2n])``;
  - ``R_t = clip(a_res mat(m_t[2n:]) + mat(b[2n:]), clamp)``, n x n
    row-major; ``M = exp(R_t)``; ``sinkhorn_iters`` times: ``M = M / (row
    sums + hyper_eps)``, then ``M = M / (column sums + hyper_eps)``;
    ``res_t = M``, differentiated through every iteration as written;
  - ``u_t = sum_j pre_t[j] X[t, j]``; ``y = F(rms_norm(u) w)``;
  - ``X'[t, i] = sum_j res_t[i, j] X[t, j] + post_t[i] y_t``.

- ``L``: latent attention in the uncompressed form, as
  ``reference_latent_seq.py`` writes it, with a head's query and key
  ``[nope | rope]`` wider than its value, and **YaRN** on the rotary
  part (the DeepSeek-V3 reading of the ``rope_scaling`` keys; d the
  rotary width, s ``factor``, P ``original_max_position_embeddings``):
  ``theta_i = base^(-2i/d)``; ``low = floor(d ln(P / (beta_fast 2 pi)) /
  (2 ln base))``, ``high = ceil(d ln(P / (beta_slow 2 pi)) / (2 ln
  base))``, both clamped to [0, d/2 - 1]; ``ramp_i = clip((i - low) /
  (high - low), 0, 1)``; the pair (x_i, x_{i + d/2}) at position t turns
  by ``t theta_i ((1 - ramp_i) + ramp_i / s)``; cos and sin are
  multiplied by ``g(mscale) / g(mscale_all_dim)`` and the scores by
  ``g(mscale_all_dim)^2 / sqrt(d_nope + d_rope)``, ``g(m) = 0.1 m ln s +
  1``. The full scores of a block of queries at a time.
- ``D`` and ``E``: ``reference_latent_seq``'s gated feed-forward and
  its held experts' part beside the shared expert, as they are.

It is computed sublayer by sublayer so that the published widths at 8192
positions fit one chip beside Adam's state: the forward hands each
sublayer's input X (470 MB in float32) to the host's memory, the
backward fetches it back, takes that sublayer's ``jax.vjp`` and hands
its gradients straight to Adam.

``precision="fp8"`` is the control: every matrix product's operands
(the maps' projection included), and every cotangent that flows back
through one, rounded to float8_e4m3 under a per-tensor scale.
``fault="unchanged"`` returns its state unchanged after every step;
``half_batch`` is the caller's.
"""

import math

from reference import _fake_quant   # float8_e4m3 rounding, no DLRM in it
from reference_hybrid_seq import _rms, head_loss
from reference_latent_seq import dense_ffn, experts
from weights_hyper_seq import hyper_leaves
from weights_latent_seq import layer_leaves


def yarn_scale(m, factor):
    """``g(m) = 0.1 m ln(factor) + 1``."""
    return 0.1 * m * math.log(factor) + 1.0


def yarn(d, base, rule):
    """(the d/2 frequencies, what multiplies cos and sin, what multiplies
    the scores beside ``1 / sqrt(head width)``) under the published
    ``rope_scaling`` record ``rule``; plain rotary where it is None."""
    plain = [base ** (-2.0 * i / d) for i in range(d // 2)]
    if rule is None:
        return plain, 1.0, 1.0
    s, span = rule["factor"], rule["original_max_position_embeddings"]

    def pair_turning(times):
        return (d * math.log(span / (times * 2 * math.pi))
                / (2 * math.log(base)))

    low = min(max(math.floor(pair_turning(rule["beta_fast"])), 0), d // 2 - 1)
    high = min(max(math.ceil(pair_turning(rule["beta_slow"])), 0), d // 2 - 1)
    freq = []
    for i, theta in enumerate(plain):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        freq.append(theta * ((1.0 - ramp) + ramp / s))
    all_dim = rule.get("mscale_all_dim", 0)
    softmax = yarn_scale(all_dim, s) ** 2 if all_dim else 1.0
    return (freq, yarn_scale(rule.get("mscale", 1), s)
            / yarn_scale(all_dim, s), softmax)


def rotate(x, freq, amplitude):
    """``x`` (batch, T, heads, d): the pair (x_i, x_{i + d/2}) at
    position t turned by the angle ``t freq_i``."""
    import jax.numpy as jnp

    t, half = x.shape[1], x.shape[-1] // 2
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None, None]
             * jnp.asarray(freq, jnp.float32))
    cos, sin = amplitude * jnp.cos(angle), amplitude * jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def latent_attention(p, u, sz, qz):
    import jax
    import jax.numpy as jnp

    bs, t, _ = u.shape
    heads, nope, rope, vd = (sz["heads"], sz["nope_dim"], sz["rope_dim"],
                             sz["v_dim"])
    freq, amplitude, softmax = yarn(rope, sz["rope_theta"],
                                    sz["rope_scaling"])
    c_q = _rms(jnp.dot(qz(u), qz(p["q_a"])), sz["eps"]) * p["q_norm"]
    q = jnp.dot(qz(c_q), qz(p["q_b"])).reshape(bs, t, heads, nope + rope)
    kva = jnp.dot(qz(u), qz(p["kv_a"]))
    c_kv = _rms(kva[..., :sz["kv_rank"]], sz["eps"]) * p["kv_norm"]
    k_rope = rotate(kva[..., None, sz["kv_rank"]:], freq, amplitude)
    kv = jnp.dot(qz(c_kv), qz(p["kv_b"])).reshape(bs, t, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope],
                         rotate(q[..., nope:], freq, amplitude)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (bs, t, heads, rope))], -1)
    q, k, v = (y.transpose(0, 2, 1, 3) for y in (q, k, kv[..., nope:]))
    block = min(t, 512)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, -t % block), (0, 0)))
    key_at = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        q_blk, first = args                     # (bs, heads, block, d)
        s = (jnp.einsum("bhqd,bhkd->bhqk", qz(q_blk), qz(k))
             * (softmax / math.sqrt(nope + rope)))
        seen = key_at[None, :] <= (first + jnp.arange(block))[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        return jnp.einsum("bhqk,bhkd->bhqd", qz(w), qz(v))

    q_blocks = jnp.moveaxis(
        q.reshape(bs, heads, -1, block, nope + rope), 2, 0)
    out = jax.lax.map(one, (q_blocks, jnp.arange(0, q.shape[2], block)))
    out = jnp.moveaxis(out, 0, 2).reshape(bs, heads, -1, vd)[:, :, :t]
    out = out.transpose(0, 2, 1, 3).reshape(bs, t, heads * vd)
    return jnp.dot(qz(out), qz(p["o_proj"]))


MIXERS = {"L": latent_attention, "D": dense_ffn, "E": experts}


def sinkhorn(logits, iters, eps):
    import jax.numpy as jnp

    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hyper_maps(p, x, sz, qz):
    """(pre (.., n), post (.., n), res (.., n, n)) of ``x`` (.., n, C)."""
    import jax.numpy as jnp

    n = sz["streams"]
    z = x.reshape(*x.shape[:-2], -1)
    rms = jnp.sqrt(jnp.mean(z * z, axis=-1, keepdims=True) + sz["hyper_eps"])

    def affine(name):
        m = jnp.dot(qz(z), qz(p[f"hyper_{name}_phi"])) / rms
        return p[f"hyper_{name}_scale"] * m + p[f"hyper_{name}_bias"]

    def sigmoid(v):
        return 1.0 / (1.0 + jnp.exp(-v))

    lo, hi = sz["hyper_clamp"]
    logits = jnp.clip(affine("res"), lo, hi)
    return (sigmoid(affine("pre")), 2.0 * sigmoid(affine("post")),
            sinkhorn(logits.reshape(*logits.shape[:-1], n, n),
                     sz["sinkhorn_iters"], sz["hyper_eps"]))


def layer(kind, p, x, sz, qz):
    import jax.numpy as jnp

    pre, post, res = hyper_maps(p, x, sz, qz)
    u = jnp.einsum("btj,btjc->btc", pre, x)
    y = MIXERS[kind](p, _rms(u, sz["eps"]) * p["norm"], sz, qz)
    return (jnp.einsum("btij,btjc->btic", res, x)
            + post[..., None] * y[:, :, None, :])


def top_loss(p, x, target, sz, qz):
    """Contraction, the final norm, the item head, the cross entropy."""
    return head_loss(p, x.sum(axis=2), target, sz, qz)


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
    pattern, n = sz["pattern"], sz["streams"]

    def fwd(kind):
        return jax.jit(lambda p, x: layer(kind, p, x, sz, qz))

    def bwd(kind):
        def f(p, x, dx):
            _, pull = jax.vjp(lambda p, x: layer(kind, p, x, sz, qz), p, x)
            return pull(dx)
        return jax.jit(f)

    @jax.jit
    def top(p, x, target):
        loss, (dp, dx) = jax.value_and_grad(
            lambda p, x: top_loss(p, x, target, sz, qz), argnums=(0, 1))(p, x)
        return loss, dp, dx

    @jax.jit
    def expand(table, rows):
        e = table[rows]
        return jnp.broadcast_to(e[:, :, None, :], (*e.shape[:2], n,
                                                   e.shape[-1]))

    @jax.jit
    def embed_grad(table, rows, dx):
        return jnp.zeros_like(table).at[rows].add(dx.sum(axis=2))

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
        mu = {name: jnp.zeros_like(v) for name, v in params.items()}
        nu = {name: jnp.zeros_like(v) for name, v in params.items()}
        losses, grad_norm = [], {}

        def update(name, g, k):
            new, m, v, norm = adam(params[name], mu[name], nu[name], g,
                                   jnp.float32(k))
            if k == 1:
                grad_norm[name] = float(norm)
            if fault != "unchanged":
                params[name], mu[name], nu[name] = new, m, v

        def of_layer(i, kind):
            names = ["norm"] + [p for p, _, _ in
                                hyper_leaves(sz) + layer_leaves(kind, sz)]
            return {name: params[f"L{i}.{name}"] for name in names}

        for k, (rows, target) in enumerate(batches, start=1):
            rows, target = jnp.asarray(rows), jnp.asarray(target)
            x, kept = expand(params["table"], rows), []
            for i, kind in enumerate(pattern):
                kept.append(jax.device_get(x))      # to the host's memory
                x = fwds[kind](of_layer(i, kind), x)
            loss, dp, dx = top({"final_norm": params["final_norm"],
                                "head": params["head"]}, x, target)
            del x
            losses.append(float(loss))
            for name, g in dp.items():
                update(name, g, k)
            for i in reversed(range(len(pattern))):
                dp, dx = bwds[pattern[i]](of_layer(i, pattern[i]),
                                          jnp.asarray(kept.pop()), dx)
                for name, g in dp.items():
                    update(f"L{i}.{name}", g, k)
            update("table", embed_grad(params["table"], rows, dx), k)
        del mu, nu      # room for a second set of leaves
        change_norm = {name: float(jnp.linalg.norm(params[name] - v))
                       for name, v in leaves().items()}
    return {"losses": losses, "grad_norm": grad_norm,
            "change_norm": change_norm}
