"""The plain reference: DLRM forward, loss, gradients and Adagrad in
straightforward ``jax.numpy`` float32 at ``highest`` matmul precision.
It imports nothing of the program and takes nothing the program made: its
weights come from ``weights.py`` and its batches from ``traffic.py``.

A table leaf is held as the block of rows the compared steps touch (the
other rows get a zero gradient, and Adagrad leaves a zero-gradient row
where it is), so a 4 GB model's reference needs megabytes.

``precision="fp8"`` is the control: the same mathematics with every
matmul operand, and every cotangent that flows back through one, rounded
to float8_e4m3 under a per-tensor scale — the step below the bfloat16
the configurations state. ``fault="half_batch"`` leaves the second half
of every batch out and takes the mean over the rest; ``fault=
"unchanged"`` is a step that returns its state unchanged (its losses are
the only thing left to read).
"""

import numpy as np


def row_index(ids0, rows, rule):
    """(batch,) row of a table for 0-based ids. ``hashed``: the program's
    device tables reserve row 0 and hash the 1-based id into the rest;
    ``exact``: one row per id."""
    ids0 = np.asarray(ids0, np.int64)
    if rule == "hashed":
        if rows < 2:
            raise ValueError("a hashed table needs two rows or more")
        return ((ids0 + 1) % (rows - 1)) + 1
    if rule == "exact":
        return ids0
    raise ValueError(f"unknown row rule {rule!r}")


def touched_rows(batches, rows, rule):
    """Per table: (touched (pad,) sorted unique rows padded by repeating
    the last one to batches*batch entries, [local (batch,) index per
    batch])."""
    tables = batches[0]["ids"].shape[1]
    pad = sum(len(b["ids"]) for b in batches)
    touched, local = [], [[] for _ in batches]
    for t in range(tables):
        per_batch = [row_index(b["ids"][:, t], rows[t], rule)
                     for b in batches]
        uniq = np.unique(np.concatenate(per_batch))
        for k, r in enumerate(per_batch):
            local[k].append(np.searchsorted(uniq, r).astype(np.int32))
        touched.append(np.concatenate(
            [uniq, np.full(pad - len(uniq), uniq[-1], np.int64)]))
    return touched, [np.stack(cols, axis=1) for cols in local]


def _fake_quant(x):
    """Round to float8_e4m3 under a per-tensor scale, forward and (for
    the cotangent) backward."""
    import jax
    import jax.numpy as jnp

    def q(v):
        s = jnp.maximum(jnp.max(jnp.abs(v)), 1e-30) / 448.0
        return (v / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    @jax.custom_vjp
    def f(v):
        return q(v)

    f.defvjp(lambda v: (q(v), None), lambda _, g: (q(g),))
    return f(x)


def forward(params, sub_tables, local_idx, dense, config, precision):
    """Prediction (batch, 1). ``params`` {leaf name: array} of the MLPs,
    ``sub_tables`` [array (touched, dim)], ``local_idx`` (batch, tables)."""
    import jax.numpy as jnp

    qz = _fake_quant if precision == "fp8" else (lambda v: v)

    def mlp(x, prefix, widths, last_linear):
        for i in range(len(widths)):
            x = (jnp.dot(qz(x), qz(params[f"{prefix}.{i}.kernel"]))
                 + params[f"{prefix}.{i}.bias"])
            if not (last_linear and i == len(widths) - 1):
                x = jnp.maximum(x, 0.0)
        return x

    bottom = mlp(dense, "bottom", config["bottom_mlp"], False)
    fields = jnp.stack([tab[local_idx[:, t]]
                        for t, tab in enumerate(sub_tables)], axis=1)
    t = jnp.concatenate([bottom[:, None, :], fields], axis=1)
    dots = jnp.einsum("bfd,bgd->bfg", qz(t), qz(t))
    iu, ju = np.triu_indices(t.shape[1], k=1)
    top_in = jnp.concatenate([bottom, dots[:, iu, ju]], axis=1)
    out = mlp(top_in, "top", config["top_mlp"], True)
    return 1.0 / (1.0 + jnp.exp(-out))


def bce(pred, label):
    import jax.numpy as jnp

    pred = jnp.clip(pred, 1e-7, 1.0 - 1e-7)
    return -jnp.mean(label * jnp.log(pred)
                     + (1.0 - label) * jnp.log(1.0 - pred))


def adagrad(p, acc, g, opt):
    """One Adagrad step as the configuration states it: ``post`` uses the
    accumulator with this gradient already in it (optax), ``pre`` the one
    from before it (the parameter server's)."""
    import jax.numpy as jnp

    lr, eps = opt["lr"], opt["eps"]
    if opt["accumulator"] == "post":
        acc2 = acc + g * g
        return p - lr * g / jnp.sqrt(acc2 + eps), acc2
    p2 = p - lr * g / jnp.sqrt(acc + eps)
    return p2, acc * opt.get("g_square_momentum", 1.0) + g * g


def first_steps(config, dense_opt, row_opt, mlp_params, sub_tables,
                local_idx, batches, precision="float32", fault=None):
    """Three (``len(batches)``) plain training steps. Returns ``losses``,
    ``grad_norm`` {leaf: norm of the first step's gradient} and
    ``change_norm`` {leaf: norm of the change over all the steps}. A step
    is one jitted function of fixed shapes, so a later run finds it in the
    compilation cache."""
    import jax
    import jax.numpy as jnp

    def loss_fn(params, idx, dense, label):
        tabs = [params[f"table.{t}"] for t in range(len(sub_tables))]
        return bce(forward(params, tabs, idx, dense, config, precision),
                   label)

    def opt_of(name):
        return row_opt if name.startswith("table.") else dense_opt

    @jax.jit
    def step(params, acc, idx, dense, label):
        loss, grads = jax.value_and_grad(loss_fn)(params, idx, dense, label)
        norms = {k: jnp.linalg.norm(g) for k, g in grads.items()}
        new = {k: adagrad(params[k], acc[k], grads[k], opt_of(k))
               for k in params}
        return (loss, norms, {k: v[0] for k, v in new.items()},
                {k: v[1] for k, v in new.items()})

    @jax.jit
    def change(params, params0):
        return {k: jnp.linalg.norm(params[k] - params0[k]) for k in params}

    params = {k: jnp.asarray(v, jnp.float32) for k, v in mlp_params.items()}
    params.update({f"table.{t}": jnp.asarray(v, jnp.float32)
                   for t, v in enumerate(sub_tables)})
    params0 = params
    acc = {k: jnp.full_like(v, opt_of(k)["initial_accumulator"])
           for k, v in params.items()}
    losses, grad_norm = [], None
    with jax.default_matmul_precision("highest"):
        for b, idx in zip(batches, local_idx):
            dense, label = b["dense"], b["label"]
            if fault == "half_batch":
                half = len(label) // 2
                dense, label, idx = dense[:half], label[:half], idx[:half]
            loss, norms, new_params, new_acc = step(
                params, acc, jnp.asarray(idx), jnp.asarray(dense),
                jnp.asarray(label))
            losses.append(float(loss))
            if grad_norm is None:
                grad_norm = {k: float(v) for k, v in norms.items()}
            if fault != "unchanged":
                params, acc = new_params, new_acc
        change_norm = {k: float(v)
                       for k, v in change(params, params0).items()}
    return {"losses": losses, "grad_norm": grad_norm,
            "change_norm": change_norm}
