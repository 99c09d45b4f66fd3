"""Placement ``toy_seq``: a test fixture that stands for a program of
another tower family. The mean of a history's item embeddings, one matrix
product onto the vocabulary in the configuration's ``matmul_dtype``, a
softmax loss on the next item, ``optax.adagrad`` over everything. The
reference below it is the same mathematics in plain float32 at
``highest`` precision with its own Adagrad; both make their weights from
the seed. It names no table shapes and no rows: the readers that need
them find nothing to read. Of the benchmark's DLRM files it takes only
``reference.py``'s float8 rounding for its control.
"""

import numpy as np

from reference import _fake_quant   # float8_e4m3 rounding, no DLRM in it

LEAVES = ("table", "kernel", "bias")


def _weights(seed, config):
    """{leaf: float32 array}, a pure function of the seed."""
    import jax
    import jax.numpy as jnp

    vocab, width = config["vocab"], config["width"]
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed % (2**31 - 1)))
    return {"table": 0.5 * jax.random.normal(k1, (vocab, width), jnp.float32),
            "kernel": jax.random.normal(k2, (width, vocab), jnp.float32)
            / np.sqrt(width),
            "bias": jnp.zeros((vocab,), jnp.float32)}


def _loss(params, history, target, cast):
    import jax
    import jax.numpy as jnp

    h = jnp.mean(params["table"][history], axis=1)
    logits = jnp.dot(cast(h), cast(params["kernel"]),
                     preferred_element_type=jnp.float32) + params["bias"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, target[:, None], axis=1))


def _norms(tree):
    import jax.numpy as jnp

    return {k: float(jnp.linalg.norm(v)) for k, v in tree.items()}


class Runner:
    def __init__(self, env):
        import jax
        import jax.numpy as jnp
        import optax

        self.env, cfg = env, env.config
        self.opt = cfg["optimizer"]
        dtype = jnp.dtype(cfg["matmul_dtype"])
        optimizer = optax.adagrad(
            self.opt["lr"],
            initial_accumulator_value=self.opt["initial_accumulator"],
            eps=self.opt["eps"])

        def train(params, opt_state, history, target):
            loss, grads = jax.value_and_grad(_loss)(
                params, history, target, lambda v: v.astype(dtype))
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        self._train = jax.jit(train)
        self.params0 = _weights(env.seed, cfg)
        self.params = self.params0
        self.opt_state = optimizer.init(self.params)
        self.program, self.events = {}, 0

    def convert(self, b):
        """Generator thread: what ``step`` takes."""
        return (b["history"].astype(np.int32), b["target"].astype(np.int32))

    def step(self, feed):
        history, target = feed
        self.events += len(target)
        self.params, self.opt_state, loss = self._train(
            self.params, self.opt_state, history, target)
        return loss

    def after_step(self, k, last):
        """The first gradient as Adagrad got it, from the state after one
        step (g = (p0 - p1) sqrt(acc1 + eps) / lr), and every leaf's
        change after the last compared step."""
        import jax.numpy as jnp

        lr, eps = self.opt["lr"], self.opt["eps"]
        if k == 1:
            acc = self.opt_state[0].sum_of_squares
            self.program["grad_norm"] = _norms({
                n: (self.params0[n] - self.params[n])
                * jnp.sqrt(acc[n] + eps) / lr for n in LEAVES})
        if k == last:
            self.program["change_norm"] = _norms({
                n: self.params[n] - self.params0[n] for n in LEAVES})

    def settled(self):
        return True

    def counters(self):
        return {"events_total": self.events}

    def table_shapes(self):
        return []

    def close(self):
        self.params = self.params0 = self.opt_state = None


def build(env):
    return Runner(env)


def reference_side(env, batches, precision="float32", fault=None):
    """Plain float32 steps over the same batches; ``precision="fp8"`` is
    the control, ``fault`` one of ``half_batch`` and ``unchanged``."""
    import jax
    import jax.numpy as jnp

    opt = env.config["optimizer"]
    lr, eps = opt["lr"], opt["eps"]
    cast = _fake_quant if precision == "fp8" else (lambda v: v)

    @jax.jit
    def step(params, acc, history, target):
        loss, grads = jax.value_and_grad(_loss)(params, history, target, cast)
        acc = {k: acc[k] + grads[k] ** 2 for k in params}
        new = {k: params[k] - lr * grads[k] / jnp.sqrt(acc[k] + eps)
               for k in params}
        return loss, grads, new, acc

    params0 = params = _weights(env.seed, env.config)
    acc = {k: jnp.full_like(v, opt["initial_accumulator"])
           for k, v in params.items()}
    out = {"losses": [], "grad_norm": None}
    with jax.default_matmul_precision("highest"):
        for b in batches:
            history, target = b["history"], b["target"]
            if fault == "half_batch":
                half = len(target) // 2
                history, target = history[:half], target[:half]
            loss, grads, new, new_acc = step(params, acc, history, target)
            out["losses"].append(float(loss))
            if out["grad_norm"] is None:
                out["grad_norm"] = _norms(grads)
            if fault != "unchanged":
                params, acc = new, new_acc
    out["change_norm"] = _norms({k: params[k] - params0[k] for k in params})
    return out
