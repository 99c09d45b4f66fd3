"""The plain reference of the latent-attention sequence tower: forward,
the two cross entropies, gradients and Adam in straightforward
``jax.numpy`` float32 at ``highest`` matmul precision. It imports
nothing of the program and takes nothing the program made: its weights
come from ``weights_latent_seq.py`` and its batches from the generator.

Every layer is ``h + mixer(rms_norm(h) * w)``:

- ``L``: latent attention in the uncompressed form. ``c_q = rms(u W_qa)
  w``; a head's query ``[q_nope | q_rope]`` from ``c_q W_qb``; ``[c_kv |
  k_r] = u W_kva``, ``c_kv = rms(c_kv) w``; a head's ``[k_nope | v]``
  from ``c_kv W_kvb``; the rotary rotation written out (rotate-half:
  feature i of the first half pairs with feature i of the second, angle
  ``t theta^(-2i/d)``) on ``q_rope`` and on the one ``k_r`` every head
  shares; the full scores of a block of queries at a time, ``softmax(q
  k^T / sqrt(d_nope + d_rope)) v``;
- ``D``: ``(silu(u W_g) * (u W_u)) W_d``, ``[W_g | W_u]`` one leaf;
- ``E``: routing over all the routed experts (``reference_hybrid_seq.
  routing``: top k of sigmoid scores, ``scaling s_e / sum``), then a
  loop (``lax.scan``) over the held expert ids, each a gated expert over
  every token under a dense mask of its routing weights, and the shared
  expert; what the experts held elsewhere would add is left out;
- the item head and the cross entropy against item t+1;
- the prediction module over positions 0..T-2 literally (no roll):
  ``h'_t = [rms(e_{t+1}) w_e | rms(h_t) w_h] W_eh``, one more block of
  its own, a norm, the **main** head matrix, cross entropy against item
  t+2. The loss is ``CE_main + mtp_weight CE_mtp``, each a mean over its
  own positions.

It is computed layer by layer so that the published widths at 8192
positions fit one chip beside Adam's state: the forward keeps each
layer's input, the backward takes one layer's ``jax.vjp`` at a time and
hands its gradients straight to Adam; the table's and the head's
gradients, which two paths feed, are summed first.

``precision="fp8"`` is the control: every matrix product's operands, and
every cotangent that flows back through one, rounded to float8_e4m3
under a per-tensor scale. ``fault="unchanged"`` returns its state
unchanged after every step; ``half_batch`` is the caller's.
"""

import math

from reference import _fake_quant   # float8_e4m3 rounding, no DLRM in it
from reference_hybrid_seq import _rms, _silu, head_loss, routing
from weights_latent_seq import layer_leaves


def rotate(x, theta):
    """``x`` (batch, T, heads, d): the pair (x_i, x_{i + d/2}) at
    position t turned by the angle ``t theta^(-2i/d)``."""
    import jax.numpy as jnp

    t, half = x.shape[1], x.shape[-1] // 2
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None, None]
             * theta ** (-jnp.arange(half, dtype=jnp.float32) / half))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            a * jnp.sin(angle) + b * jnp.cos(angle)], -1)


def latent_attention(p, u, sz, qz):
    import jax
    import jax.numpy as jnp

    bs, t, _ = u.shape
    heads, nope, rope, vd = (sz["heads"], sz["nope_dim"], sz["rope_dim"],
                             sz["v_dim"])
    c_q = _rms(jnp.dot(qz(u), qz(p["q_a"])), sz["eps"]) * p["q_norm"]
    q = jnp.dot(qz(c_q), qz(p["q_b"])).reshape(bs, t, heads, nope + rope)
    kva = jnp.dot(qz(u), qz(p["kv_a"]))
    c_kv = _rms(kva[..., :sz["kv_rank"]], sz["eps"]) * p["kv_norm"]
    k_rope = rotate(kva[..., None, sz["kv_rank"]:], sz["rope_theta"])
    kv = jnp.dot(qz(c_kv), qz(p["kv_b"])).reshape(bs, t, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope],
                         rotate(q[..., nope:], sz["rope_theta"])], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (bs, t, heads, rope))], -1)
    q, k, v = (y.transpose(0, 2, 1, 3) for y in (q, k, kv[..., nope:]))
    # whole blocks of queries: those past the end see every key, and
    # are cut off again below (a module's T - 1 positions may be prime)
    block = min(t, 512)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, -t % block), (0, 0)))
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
        q.reshape(bs, heads, -1, block, nope + rope), 2, 0)
    out = jax.lax.map(one, (q_blocks, jnp.arange(0, q.shape[2], block)))
    out = jnp.moveaxis(out, 0, 2).reshape(bs, heads, -1, vd)[:, :, :t]
    out = out.transpose(0, 2, 1, 3).reshape(bs, t, heads * vd)
    return jnp.dot(qz(out), qz(p["o_proj"]))


def gated(x, w1, w2, qz):
    """``(silu(x W_g) * (x W_u)) W_d`` with ``w1 = [W_g | W_u]``."""
    import jax.numpy as jnp

    width = w2.shape[0]
    pre = jnp.dot(qz(x), qz(w1))
    return jnp.dot(qz(_silu(pre[..., :width]) * pre[..., width:]), qz(w2))


def dense_ffn(p, u, sz, qz):
    return gated(u, p["gate_up"], p["down"], qz)


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
        return out + w_e[:, None] * gated(tokens, w1, w2, qz), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(tokens),
                          (jnp.asarray(held), p["w1"], p["w2"]))
    return (out + shared_expert(p, tokens, qz)).reshape(bs, t, hidden)


def shared_expert(p, tokens, qz):
    return gated(tokens, p["shared_w1"], p["shared_w2"], qz)


MIXERS = {"L": latent_attention, "D": dense_ffn, "E": experts}


def layer(kind, p, h, sz, qz):
    return h + MIXERS[kind](p, _rms(h, sz["eps"]) * p["norm"], sz, qz)


def merge(p, h, rows, sz, qz):
    """The prediction module's input over positions 0..T-2: the rows of
    the items at 1..T-1 beside the hidden states at 0..T-2."""
    import jax.numpy as jnp

    e = _rms(rows[:, 1:], sz["eps"]) * p["embed_norm"]
    u = _rms(h[:, :-1], sz["eps"]) * p["hidden_norm"]
    return jnp.dot(qz(jnp.concatenate([e, u], axis=-1)), qz(p["merge"]))


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
    pattern, ahead = sz["pattern"], sz["mtp_pattern"] * bool(sz["mtp_depth"])
    lam = sz["mtp_weight"]

    def fwd(kind):
        return jax.jit(lambda p, h: layer(kind, p, h, sz, qz))

    def bwd(kind):
        def f(p, h, dh):
            _, pull = jax.vjp(lambda p, h: layer(kind, p, h, sz, qz), p, h)
            return pull(dh)
        return jax.jit(f)

    @jax.jit
    def top(p, h, target, weight):
        loss, (dp, dh) = jax.value_and_grad(
            lambda p, h: weight * head_loss(p, h, target, sz, qz),
            argnums=(0, 1))(p, h)
        return loss, dp, dh

    merge_fwd = jax.jit(lambda p, h, rows: merge(p, h, rows, sz, qz))

    @jax.jit
    def merge_bwd(p, h, rows, d):
        _, pull = jax.vjp(lambda p, h, rows: merge(p, h, rows, sz, qz),
                          p, h, rows)
        return pull(d)

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
        fwds = {k: fwd(k) for k in set(pattern + ahead)}
        bwds = {k: bwd(k) for k in set(pattern + ahead)}
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

        def of_layer(prefix, kind):
            return {n: params[f"{prefix}.{n}"]
                    for n in ["norm"] + [n for n, _, _ in
                                         layer_leaves(kind, sz)]}

        def through(prefix, kinds, h):
            """The layers ``kinds`` named ``<prefix>L<i>`` over ``h``:
            every layer's input, then the output."""
            hs = [h]
            for i, kind in enumerate(kinds):
                hs.append(fwds[kind](of_layer(f"{prefix}L{i}", kind),
                                     hs[-1]))
            return hs

        def back(prefix, kinds, hs, dh, k):
            """Carries ``dh`` back through them, each layer's gradients
            to Adam as they come; ``hs`` as ``through`` left it, less
            the output."""
            for i in reversed(range(len(kinds))):
                dp, dh = bwds[kinds[i]](of_layer(f"{prefix}L{i}", kinds[i]),
                                        hs.pop(), dh)
                for n, g in dp.items():
                    update(f"{prefix}L{i}.{n}", g, k)
            return dh

        for k, (rows, target) in enumerate(batches, start=1):
            rows, target = jnp.asarray(rows), jnp.asarray(target)
            embedded = params["table"][rows]
            hs = through("", pattern, embedded)
            last = hs.pop()
            loss, dp, dh = top({"final_norm": params["final_norm"],
                                "head": params["head"]}, last, target,
                               jnp.float32(1.0))
            d_rows = None
            if ahead:
                names = ("embed_norm", "hidden_norm", "merge")
                p_merge = {n: params[f"mtp.{n}"] for n in names}
                hs_a = through("mtp.", ahead,
                               merge_fwd(p_merge, last, embedded))
                loss_a, dp_a, dh_a = top(
                    {"final_norm": params["mtp.head_norm"],
                     "head": params["head"]}, hs_a.pop(), target[:, 1:],
                    jnp.float32(lam))
                loss = loss + loss_a
                dp["head"] = dp["head"] + dp_a["head"]
                update("mtp.head_norm", dp_a["final_norm"], k)
                dh_a = back("mtp.", ahead, hs_a, dh_a, k)
                dp_m, dh_last, d_rows = merge_bwd(p_merge, last, embedded,
                                                  dh_a)
                for n in names:
                    update(f"mtp.{n}", dp_m[n], k)
                dh = dh + dh_last
            losses.append(float(loss))
            for n, g in dp.items():
                update(n, g, k)
            dh = back("", pattern, hs, dh, k)
            d_rows = dh if d_rows is None else d_rows + dh
            update("table", embed_grad(params["table"], rows, d_rows), k)
        del mu, nu      # room for a second set of leaves
        change_norm = {n: float(jnp.linalg.norm(params[n] - v))
                       for n, v in leaves().items()}
    return {"losses": losses, "grad_norm": grad_norm,
            "change_norm": change_norm}
