"""The plain reference of the selected-attention sequence tower:
forward, next-item cross entropy plus the indexer's alignment loss,
gradients and Adam in straightforward ``jax.numpy`` float32 at
``highest`` matmul precision. It imports nothing of the program and
takes nothing the program made: its weights come from
``weights_sparse_seq.py`` and its batches from the generator.

Every layer is ``h + mixer(rms_norm(h) * w)``, ``u`` the normed input:

- ``S``: ``q = u W_q``, ``k = u W_k``, ``v = u W_v``; an RMS norm a head
  on ``q`` and on ``k`` (weights of ``head_dim``); the rotary rotation
  written out (``reference_latent_seq.rotate``: rotate-half, angle ``t
  theta^(-2i/d)``) over the whole of every query and key head. The
  indexer, on ``u`` held constant: ``q_i = u W_iq`` a head ``index_dim``
  wide, one key ``k_i = layer_norm(u W_ik)`` (mean and variance over its
  ``index_dim`` features, scale and bias), ``w = u W_iw`` times
  ``index_heads^(-1/2) index_dim^(-1/2)``, the first ``index_rope_dim``
  features of every ``q_i`` head and of ``k_i`` rotated; ``I[t, s] =
  sum_j w[t, j] relu(q_i[t, j] . k_i[s])``, **the dense scores of a
  block of queries at a time**. The selection of query ``t``: every
  causal key ranked by descending ``I`` (a stable sort, so that equal
  scores go to the earlier position; ``-0.0`` counts as ``0.0``), the
  ``min(t + 1, topk)`` first. Attention is a softmax over the selected
  keys of ``q k^T / sqrt(head_dim)``, a key-value head serving ``heads /
  kv_heads`` query heads; the mixer returns ``concat_h(o) W_o``. The
  alignment loss: ``p[t, .]`` the heads' probabilities averaged, a
  constant; ``r[t, .]`` the softmax of ``I[t, .]`` over the selected
  keys; ``L = mean_t sum_s p log(p / r)``. The step's loss is the cross
  entropy plus ``index_loss_weight`` times the sum of ``L`` over the
  ``S`` layers; the indexer's leaves learn from ``L`` alone;
- ``E``: scores ``softmax(u W_r)`` over all the routed experts, the top
  ``experts_per_token``, weights ``s_e / sum of the chosen s``; a loop
  (``lax.scan``) over the held expert ids, each a gated expert over
  every token under a dense mask of its routing weights; no shared
  expert; what the experts held elsewhere would add is left out;
- the item head and the cross entropy against item t+1
  (``reference_hybrid_seq.head_loss``).

The reference selects for itself, from its own index scores at its own
float32 hidden states; the program's bfloat16 states move 0.5 to 0.9 %
of a layer's choices at the cut (PERF.md section 6, "PR 41"), which the
gaps the comparison reads do not feel.

It is computed layer by layer so that the published widths at 8192
positions fit one chip beside Adam's state: the forward keeps each
layer's input, the backward takes one layer's ``jax.vjp`` at a time and
hands its gradients straight to Adam.

``precision="fp8"`` is the control: every matrix product's operands
(the indexer's among them), and every cotangent that flows back through
one, rounded to float8_e4m3 under a per-tensor scale. ``fault=
"unchanged"`` returns its state unchanged after every step;
``half_batch`` is the caller's.
"""

import math

from reference import _fake_quant   # float8_e4m3 rounding, no DLRM in it
from reference_hybrid_seq import _block_of, _rms, head_loss
from reference_latent_seq import gated, rotate
from weights_sparse_seq import layer_leaves


def routing(u, router, sz):
    """(chosen (tokens, k) expert ids, weights (tokens, k)) over all the
    routed experts: top k of softmax scores, weights ``s_e / sum of the
    chosen s``."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.softmax(jnp.dot(u, router), axis=-1)
    _, chosen = jax.lax.top_k(scores, sz["experts_per_token"])
    s = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, s / jnp.sum(s, -1, keepdims=True)


def experts(p, u, sz, qz, held=None):
    """The held experts' part. ``held`` (ids) defaults to the sizes';
    ``p["w1"][i]`` is expert ``held[i]``'s."""
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
    return out.reshape(bs, t, hidden)


def rotate_first(x, width, theta):
    """``x`` (batch, T, heads, d) with its first ``width`` features
    rotated."""
    import jax.numpy as jnp

    return jnp.concatenate([rotate(x[..., :width], theta), x[..., width:]],
                           -1)


def indexer(p, u, sz, qz):
    """``q_i`` (batch, T, index heads, index_dim), ``k_i`` (batch, T,
    index_dim) and ``w`` (batch, T, index heads) from ``u``, which takes
    no gradient from them."""
    import jax
    import jax.numpy as jnp

    bs, t, _ = u.shape
    ih, idim = sz["index_heads"], sz["index_dim"]
    u = jax.lax.stop_gradient(u)
    q_i = jnp.dot(qz(u), qz(p["index_q"])).reshape(bs, t, ih, idim)
    k_i = jnp.dot(qz(u), qz(p["index_k"]))
    k_i = k_i - jnp.mean(k_i, axis=-1, keepdims=True)
    k_i = (k_i / jnp.sqrt(jnp.mean(k_i * k_i, axis=-1, keepdims=True)
                          + sz["eps"]) * p["index_k_scale"]
           + p["index_k_bias"])
    w = jnp.dot(qz(u), qz(p["index_w"])) / math.sqrt(ih * idim)
    turn = sz["index_rope_dim"]
    return (rotate_first(q_i, turn, sz["rope_theta"]),
            rotate_first(k_i[:, :, None, :], turn, sz["rope_theta"])[:, :, 0],
            w)


def rank_select(scores, live, topk):
    """bool like ``scores`` (.., rows, T): the ``topk`` first of each
    row's ``live`` entries by descending score, equal scores to the
    earlier position."""
    import jax.numpy as jnp

    x = jnp.where(live, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    order = jnp.argsort(-x, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)      # each entry's place in the order
    return (rank < topk) & live


def selected_attention(p, u, sz, qz):
    """(the mixer's output, the alignment loss)."""
    import jax
    import jax.numpy as jnp

    bs, t, _ = u.shape
    heads, kv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    topk, theta = sz["topk"], sz["rope_theta"]

    def by_head(y, n, norm):
        y = y.reshape(bs, t, n, hd)
        if norm is not None:
            y = rotate(_rms(y, sz["eps"]) * norm, theta)
        return y.transpose(0, 2, 1, 3)

    q = by_head(jnp.dot(qz(u), qz(p["q_proj"])), heads, p["q_norm"])
    k = jnp.repeat(by_head(jnp.dot(qz(u), qz(p["k_proj"])), kv, p["k_norm"]),
                   heads // kv, axis=1)
    v = jnp.repeat(by_head(jnp.dot(qz(u), qz(p["v_proj"])), kv, None),
                   heads // kv, axis=1)
    q_i, k_i, w = indexer(p, u, sz, qz)
    block = _block_of(t, 256)
    key_at = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        q_blk, qi_blk, w_blk, first = args
        index = jnp.sum(
            jnp.maximum(jnp.einsum("bqhd,bkd->bhqk", qz(qi_blk), qz(k_i)),
                        0.0) * jnp.moveaxis(w_blk, 2, 1)[..., None], axis=1)
        query = first + jnp.arange(block)
        live = key_at[None, :] <= query[:, None]
        chosen = rank_select(jax.lax.stop_gradient(index), live, topk)
        s = (jnp.einsum("bhqd,bhkd->bhqk", qz(q_blk), qz(k))
             / math.sqrt(hd))
        s = jnp.where(chosen[:, None], s, -jnp.inf)
        a = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        a = a / jnp.sum(a, axis=-1, keepdims=True)
        out = jnp.einsum("bhqk,bhkd->bhqd", qz(a), qz(v))
        target = jax.lax.stop_gradient(jnp.mean(a, axis=1))
        scores = jnp.where(chosen, index, -jnp.inf)
        log_r = scores - jax.nn.logsumexp(scores, axis=-1, keepdims=True)
        seen = target > 0
        loss = jnp.sum(jnp.where(
            seen, target * (jnp.log(jnp.where(seen, target, 1.0)) - log_r),
            0.0))
        return out, loss

    def blocks(x, axis):    # (.., T at `axis`, ..) -> (T / block, .., block, ..)
        x = x.reshape(x.shape[:axis] + (t // block, block)
                      + x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)

    out, loss = jax.lax.map(one, (blocks(q, 2), blocks(q_i, 1), blocks(w, 1),
                                  jnp.arange(0, t, block)))
    out = jnp.moveaxis(out, 0, 2).reshape(bs, heads, t, hd)
    out = out.transpose(0, 2, 1, 3).reshape(bs, t, heads * hd)
    return jnp.dot(qz(out), qz(p["o_proj"])), jnp.sum(loss) / (bs * t)


def layer(kind, p, h, sz, qz):
    """``E``: the layer's output. ``S``: (output, alignment loss)."""
    u = _rms(h, sz["eps"]) * p["norm"]
    if kind == "E":
        return h + experts(p, u, sz, qz)
    y, loss = selected_attention(p, u, sz, qz)
    return h + y, loss


def first_steps(sz, opt, leaves, batches, precision="float32", fault=None):
    """``len(batches)`` plain training steps. ``leaves()`` makes the
    initial leaves {name: float32 array} from the seed, anew at every
    call; a batch is ``(rows, target)``, both (histories, T) int: the
    table row of each event and of the one that follows it. Returns
    ``losses`` (cross entropy plus the weighted alignment loss),
    ``grad_norm`` {leaf: norm of the first step's gradient},
    ``change_norm`` {leaf: norm of the change over all the steps} and
    ``index_losses`` (a step's summed alignment loss). ``opt``: Adam's
    ``lr``, ``b1``, ``b2``, ``eps``."""
    import jax
    import jax.numpy as jnp

    qz = _fake_quant if precision == "fp8" else (lambda v: v)
    pattern, lam = sz["pattern"], sz["index_loss_weight"]

    def fwd(kind):
        return jax.jit(lambda p, h: layer(kind, p, h, sz, qz))

    def bwd(kind):
        def f(p, h, dh):
            _, pull = jax.vjp(lambda p, h: layer(kind, p, h, sz, qz), p, h)
            # an `S` layer's second output takes the loss's weight
            return pull(dh if kind == "E" else (dh, jnp.float32(lam)))
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
        losses, index_losses, grad_norm = [], [], {}

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
            hs, index_loss = [params["table"][rows]], 0.0
            for i, kind in enumerate(pattern):
                h = fwds[kind](of_layer(i), hs[-1])
                if kind == "S":
                    h, part = h
                    index_loss += float(part)
                hs.append(h)
            loss, dp, dh = top({n: params[n] for n in ("final_norm", "head")},
                               hs.pop(), target)
            losses.append(float(loss) + lam * index_loss)
            index_losses.append(index_loss)
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
            "change_norm": change_norm, "index_losses": index_losses}
