"""Hybrid sequence tower: state-space and delta-rule mixers, sparse
experts beside a shared expert (or none), grouped-query (full or over a
sliding window), latent and indexer-selected attention and a gated
dense feed-forward over a history of item ids, with an item head
for next-item prediction and, optionally, one multi-token-prediction
module for the item after next.

A generative recommender: the history is the sequence, the item table
(a ``DeviceEmbeddingCollection`` slot with ``pooling="none"``) is the
vocabulary, and the tower returns a logit for every item at every
position (``parallel.train.next_item_cross_entropy`` is the loss). It is
driven like every other tower, through ``DeviceModeModel(slot_specs=[item
slot], tower=HybridSequenceTower(...), pooling="none")`` and
``make_device_mode_trainer``.

Every layer is ``h + mixer(RMSNorm(h))`` over one residual stream, or
over ``residual_streams`` of them a hyper-connected layer
(:class:`_HyperLayer`: the mixer's input read out of the streams, its
output written back to each, the streams mixed by a doubly stochastic
map, all three input-dependent; the item's row is copied to every stream
before the first layer and the streams are summed before the final
norm). ``pattern`` names the mixers, one letter a layer (a published
transformer block, attention then a feed-forward, is two letters):

``M``  a Mamba-2 mixer: one input projection to a gate ``z``, the
       convolved stream ``xBC`` and a step size ``dt`` a head; a causal
       depthwise convolution and SiLU (``ops.short_conv``'s plain
       form); the selective scan (``ops.ssm_scan``); ``GroupRMSNorm(y * silu(z))``;
       an output projection.
``E``  an expert layer (:class:`SparseExperts`), its experts' form
       chosen by ``expert_activation``: ``relu2`` (a squared relu
       between two matrices) or ``swiglu`` (``silu(gate) * up`` between
       three, gate and up held as one).
``*``  causal grouped-query attention through the Pallas flash kernel,
       without a positional encoding: in a pattern with state-space
       layers those carry position.
``W``  grouped-query attention over a sliding window of ``attn_window``
       keys (:class:`GroupedQueryAttention` with a ``window``: the
       flash kernels walk only the band's block pairs), every query and
       key head rotated whole by plain :func:`rotary` at ``rope_theta``.
``A``  causal grouped-query attention over every earlier key, rotated
       whole at ``rope_theta`` under ``rope_scaling`` (a
       :class:`YarnRule` or None): a published sliding-window model's
       full layers beside its ``W`` ones.
``L``  causal multi-head latent attention (:class:`LatentAttention`):
       low-rank query and key-value projections with a norm on each
       latent (or one direct query projection, ``latent_q_rank`` None),
       and rotary position embedding (:func:`rotary`, plain or under a
       :class:`YarnRule`) on a decoupled part of every query head and
       on one key shared by all the heads (or none, ``latent_positions``
       False: those features enter the scores as projected, and
       position is the ``K`` layers' to carry); the same flash kernel,
       queries and keys at the head's whole key width and values at
       their own.
``K``  Kimi Delta Attention (:class:`DeltaAttention`): query, key and
       value projections each through a short causal convolution and
       SiLU, queries and keys normed a head, a decay a key channel and
       a step size a head from the input, the gated delta rule
       (``ops.kda_scan``), a gated norm a head, an output projection.
``D``  a gated dense feed-forward (:class:`GatedFeedForward`).
``S``  causal grouped-query attention over the keys a learned indexer
       selects (:class:`SelectedAttention`): queries and keys normed a
       head and rotated whole, an indexer of its own (``ops.
       sparse_select``) that scores every causal key for every query,
       the ``select_topk`` best of them attended through the flash
       kernel under a mask a (query, key)
       (``flash_attention_selected``), and the indexer's alignment loss
       as a second output: a tower with an ``S`` returns ``(logits,
       index_loss)``, the loss summed over its ``S`` layers
       (``parallel.train.next_item_cross_entropy_indexed``).

``mtp_depth`` 1 adds a :class:`NextPrediction` module after the last
layer: it merges the last hidden state (before the final norm) with the
item slot's rows rolled left by one position, runs one more block of its
own like the tower's last (the pattern's last two letters, attention and
its feed-forward), and reads the tower's own item head, so the tower
returns two sets of logits, for item t+1 and item t+2
(``parallel.train.next_items_cross_entropy``). ``mtp_depth`` 0 is the
tower without it.

Histories are left-aligned: padding, if any, lies at the tail, where
under causal mixing it reaches no real position, so no mixer masks it;
the loss leaves out positions without a target.

**The expert layer's contract.** ``SparseExperts`` is told how many
experts the model routes over (``experts_routed``) and which of them it
holds (``experts_held``, ids). It scores every token against all routed
experts in float32, takes the top ``per_token`` and their normalised
weights as the whole model would, keeps the (token, expert) pairs whose
expert is held, sorts them by expert and runs them through the grouped
product over the held experts' matrices, and adds the shared expert for
every token. What the experts held elsewhere would add is left out: in
an expert-parallel job that part arrives by an exchange with the chips
that hold them, and on one chip there is no exchange and nothing stands
in for it. No pair is ever dropped whatever the imbalance, and the work
follows the pairs routed here: they sort first, and ``dispatch_pairs``
gathers, multiplies and sums them back a buffer of
``pair_buffer_rows(tokens, per_token, held, routed)`` rows at a time (a
size from static shapes alone: twice the pairs expected at the held
experts, at least one a token), in a loop that runs as many times as
the batch's held pairs fill that buffer, a count read on the device:
once for a batch routed evenly, tokens x per_token / rows times for one
routed wholly here (``routed_rows``, which the layer sows, says which:
the passes are its sum over that size, rounded up). A loop of that kind
has no derivative of JAX's making, so ``dispatch_pairs`` brings its own:
the same loop, each buffer recomputed and carried back by the grouped
product's own backward calls (what ``nn.remat`` recomputes of the
forward loop is then dead code, so a step still runs a buffer forward
twice). One body at one size is all the step holds of the grouped
product, whose grid visits only the tiles of rows in use. The rows move
into the buffer and back onto the tokens through ``ops.expert_rows``'
kernels, which stop at the buffer's held rows, where the hidden width is
whole lane tiles and the buffer whole blocks of sixteen rows; through
XLA's gather and scatter-add elsewhere (the shapes alone decide).

Recomputation: each layer is wrapped in ``nn.remat``, so the backward
pass holds one (batch, T, hidden) input a layer, (batch, T, streams *
hidden) over several streams, and rebuilds a layer's internals when it
reaches it (one forward more a step). An attention layer also keeps the
flash kernel's ``out`` and ``lse`` (``flash_attention.RESIDUAL_NAMES``,
the remat policy here): (batch, heads, T, value width) more in the
compute dtype and a float32 row a head, for which the rebuilt layer runs
its projections and rotations again but not the kernel, whose forward
call is a quarter of the layer's attention time. A ``K`` layer keeps the
recurrence's output and the states that enter its chunks
(``kda_scan.RESIDUAL_NAMES``, the same policy): (batch, T, heads, head
width) in float32 and (batch, heads, T / chunk, head width, head width)
in the compute dtype, 268 MB a layer at 8192 positions and 32 heads of
128, for which the rebuilt layer runs its projections, convolutions and
gates again but not the recurrence's forward kernel, and the backward
kernel carries no state forward again. An ``S`` layer keeps, besides
``out`` and ``lse``, its selection ((batch, T, T) int8, 67 MB at 8192)
and its alignment loss's gradients to the indexer's queries, keys and
weights (``sparse_select.RESIDUAL_NAMES``): the rebuilt layer runs its
projections, norms and rotations again but neither the index scores and
the selection nor the pass over the target.

``init`` declares every parameter and runs no mixer: a trainer that
initialises eagerly (``make_device_mode_trainer``) would otherwise
compile each layer's forward once more, op by op, for values it throws
away (190 s of a cold start on a v5e). No parameter's shape depends on
the input's length.
"""

import functools
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import lax

from persia_tpu.ops.kda_scan import (
    RESIDUAL_NAMES as KDA_RESIDUAL_NAMES,
    kda_gate,
    kda_scan,
)
from persia_tpu.ops.sparse_select import (
    RESIDUAL_NAMES as SELECT_RESIDUAL_NAMES,
)
from persia_tpu.ops.short_conv import short_conv, short_conv_plain
from persia_tpu.ops.ssm_scan import ssm_scan

F32 = jnp.float32


def _dense(x, w, dtype, out=None):
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=out or dtype)


def _rms(x, eps):
    """x / sqrt(mean(x^2) + eps) over the last axis, float32."""
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _kernel_init(scale=1.0):
    return nn.initializers.variance_scaling(scale, "fan_in", "normal")


class RMSNorm(nn.Module):
    eps: float = 1e-5
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],), F32)
        return (_rms(x, self.eps) * w).astype(self.compute_dtype)


def _a_log_init(key, shape, dtype=F32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(dt_min, dt_max, dt_floor):
    def init(key, shape, dtype=F32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                     * (np.log(dt_max) - np.log(dt_min)) + np.log(dt_min))
        dt = jnp.maximum(dt, dt_floor)
        return dt + jnp.log(-jnp.expm1(-dt))    # softplus's inverse
    return init


class SSMMixer(nn.Module):
    """Mamba-2 mixer over (batch, T, hidden)."""

    heads: int = 64
    head_dim: int = 64
    groups: int = 8
    state: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    eps: float = 1e-5
    dt_limits: Sequence[float] = (1e-3, 1e-1, 1e-4)   # min, max, floor
    out_scale: float = 1.0
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        bs, t, hidden = u.shape
        cd = self.compute_dtype
        inner, gn = self.heads * self.head_dim, self.groups * self.state
        conv_dim = inner + 2 * gn
        w_in = self.param("in_proj", _kernel_init(),
                          (hidden, inner + conv_dim + self.heads), F32)
        conv_w = self.param(
            "conv_w", nn.initializers.uniform(2.0 / np.sqrt(self.conv_kernel)),
            (self.conv_kernel, conv_dim), F32)
        conv_b = self.param("conv_b", nn.initializers.zeros, (conv_dim,), F32)
        dt_bias = self.param("dt_bias", _dt_bias_init(*self.dt_limits),
                             (self.heads,), F32)
        a_log = self.param("A_log", _a_log_init, (self.heads,), F32)
        d = self.param("D", nn.initializers.ones, (self.heads,), F32)
        norm_w = self.param("norm_w", nn.initializers.ones, (inner,), F32)
        w_out = self.param("out_proj", _kernel_init(self.out_scale),
                           (inner, hidden), F32)
        if self.is_initializing():
            return jnp.zeros_like(u)

        zxbcdt = _dense(u, w_in, cd)
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
        with jax.named_scope("ssm_conv"):
            # the plain form: XLA fuses it into the split and the scan's
            # inputs, where the kernel's one output costs the scan more
            # than the kernel saves (PERF.md §6, "PR 43")
            xbc = short_conv_plain(xbc, conv_w, conv_b, out_dtype=cd)
        x, b, c = jnp.split(xbc, [inner, inner + gn], axis=-1)
        x = x.reshape(bs, t, self.heads, self.head_dim)
        dt = jax.nn.softplus(dt.astype(F32) + dt_bias)
        with jax.named_scope("ssm_scan"):
            y = ssm_scan(x, dt, -jnp.exp(a_log),
                         b.reshape(bs, t, self.groups, self.state),
                         c.reshape(bs, t, self.groups, self.state),
                         chunk=self.chunk, compute_dtype=cd)
        y = y + d[:, None] * x.astype(F32)
        y = y.reshape(bs, t, inner) * nn.silu(z.astype(F32))
        y = _rms(y.reshape(bs, t, self.groups, inner // self.groups),
                 self.eps).reshape(bs, t, inner) * norm_w
        return _dense(y, w_out, cd)


class DeltaAttention(nn.Module):
    """Kimi Delta Attention over (batch, T, hidden): ``heads`` heads
    whose keys and values are both ``head_dim`` wide.

    ``q``, ``k``, ``v`` = ``silu(conv(u W))``, each with its own
    projection and its own ``conv_kernel`` taps (no bias); a head's
    query and key over ``sqrt(sum of squares + L2_EPS)``, the query
    times ``head_dim^(-1/2)``: convolution, SiLU and norm are one op,
    ``ops.short_conv``. The decay, a value a key channel, in
    float32: ``g = -exp(A_log[head]) softplus((u f_a) f_b + dt_bias)``
    through a bottleneck as wide as a head; the step size ``beta =
    sigmoid(u b_proj)``, one a head. ``o`` is the gated delta rule's
    (``ops.kda_scan``: the state forgets a channel at a time and is
    corrected, under each key, towards the value). The mixer returns
    ``(RMSNorm_head(o) o_norm * sigmoid((u g_a) g_b)) o_proj``."""

    heads: int = 32
    head_dim: int = 128
    conv_kernel: int = 4
    chunk: int = 64
    eps: float = 1e-5
    dt_limits: Sequence[float] = (1e-3, 1e-1, 1e-4)   # min, max, floor
    out_scale: float = 1.0
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        bs, t, hidden = u.shape
        cd, heads, hd = self.compute_dtype, self.heads, self.head_dim
        inner = heads * hd
        proj, conv = {}, {}
        for name in "qkv":
            proj[name] = self.param(f"{name}_proj", _kernel_init(),
                                    (hidden, inner), F32)
            conv[name] = self.param(
                f"{name}_conv",
                nn.initializers.uniform(2.0 / np.sqrt(self.conv_kernel)),
                (self.conv_kernel, inner), F32)
        f_a = self.param("f_a", _kernel_init(), (hidden, hd), F32)
        f_b = self.param("f_b", _kernel_init(), (hd, inner), F32)
        dt_bias = self.param("dt_bias", _dt_bias_init(*self.dt_limits),
                             (inner,), F32)
        a_log = self.param("A_log", _a_log_init, (heads,), F32)
        b_proj = self.param("b_proj", _kernel_init(), (hidden, heads), F32)
        g_a = self.param("g_a", _kernel_init(), (hidden, hd), F32)
        g_b = self.param("g_b", _kernel_init(), (hd, inner), F32)
        o_norm = self.param("o_norm", nn.initializers.ones, (hd,), F32)
        w_out = self.param("o_proj", _kernel_init(self.out_scale),
                           (inner, hidden), F32)
        if self.is_initializing():
            return jnp.zeros_like(u)

        def by_head(x):
            return x.reshape(bs, t, heads, hd)

        with jax.named_scope("kda_project"):
            q, k, v = (_dense(u, proj[name], cd) for name in "qkv")
        with jax.named_scope("kda_conv"):
            q = short_conv(q, conv["q"], head_dim=hd, scale=hd ** -0.5,
                           out_dtype=cd)
            k = short_conv(k, conv["k"], head_dim=hd, out_dtype=cd)
            v = short_conv(v, conv["v"], out_dtype=cd)
            q, k, v = by_head(q), by_head(k), by_head(v)
        with jax.named_scope("kda_gates"):
            g = kda_gate(by_head(_dense(_dense(u, f_a, cd), f_b, cd, out=F32)),
                         a_log, dt_bias)
            beta = jax.nn.sigmoid(_dense(u, b_proj, cd, out=F32))
            gate = jax.nn.sigmoid(_dense(_dense(u, g_a, cd), g_b, cd,
                                         out=F32))
        with jax.named_scope("kda_scan"):   # the kernels' name in a trace
            o = kda_scan(q, k, v, g, beta, chunk=self.chunk,
                         compute_dtype=cd)
        with jax.named_scope("kda_out"):
            y = (_rms(o, self.eps) * o_norm).reshape(bs, t, inner) * gate
            return _dense(y, w_out, cd)


def route(scores, per_token, scaling):
    """Top ``per_token`` of ``scores`` a token, and the weights ``scaling
    * s_e / sum of the chosen s``: (experts, weights), both (tokens,
    per_token). The published training adds a balancing bias to the
    scores before the choice (never to the weights) and moves it by a
    rule of its own; it is zero here and held constant."""
    _, chosen = lax.top_k(scores, per_token)
    s = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, scaling * s / jnp.sum(s, axis=-1, keepdims=True)


def pair_buffer_rows(tokens, per_token, held, routed):
    """Rows of the buffer an expert layer takes its held pairs through at
    a time, from static shapes alone: twice the pairs expected at the
    held experts under even routing, at least one a token, at most all
    ``tokens * per_token``. A granularity, not a capacity: a batch with
    more held pairs takes more passes of ``dispatch_pairs``' loop."""
    twice_expected = -(-2 * tokens * per_token * held // routed)
    return min(tokens * per_token, max(tokens, twice_expected))


def _buffers(order, sizes, rows):
    """How many buffers of ``rows`` pairs the held pairs fill, and
    ``at(i)``: the i-th buffer's first position in the sorted order and
    its group sizes (each held group's part of it, then the rest)."""
    here = sizes[:-1]
    ends = jnp.cumsum(here)

    def at(i):
        lo = i * rows
        part = jnp.clip(jnp.minimum(ends, lo + rows)
                        - jnp.maximum(ends - here, lo), 0)
        return lo, jnp.append(part, rows - jnp.sum(part))

    return (ends[-1] + rows - 1) // rows, at


def _sorted_pairs(order, weight, per_token):
    """Each sorted position's token and routing weight, made once for
    every buffer: a buffer's are then slices of them."""
    return order // per_token, weight[order]


def _relu2(pre):
    return jnp.square(nn.relu(pre))


def _relu2_pullback(pre, g):
    return g * (2 * nn.relu(pre))


def _swiglu(pre):
    """``silu(gate) * up`` of ``pre = [gate | up]``, worked out in
    float32 and returned in ``pre``'s dtype."""
    gate, up = jnp.split(pre.astype(F32), 2, axis=-1)
    return (nn.silu(gate) * up).astype(pre.dtype)


def _swiglu_pullback(pre, g):
    gate, up = jnp.split(pre.astype(F32), 2, axis=-1)
    s, g = jax.nn.sigmoid(gate), g.astype(F32)
    return jnp.concatenate([g * up * s * (1 + gate * (1 - s)),
                            g * gate * s], axis=-1).astype(pre.dtype)


class Activation(NamedTuple):
    """An expert's activation between its two products, its derivative
    written out for ``dispatch_pairs``' hand-made backward, and the
    factor by which the first product is wider than the second's input."""

    forward: Callable
    pullback: Callable
    wider: int


# ``relu2`` squares a relu (two matrices an expert); ``swiglu`` gates the
# second half of the first product's columns by the silu of the first
# half (gate and up held as one matrix: three an expert)
ACTIVATIONS = {"relu2": Activation(_relu2, _relu2_pullback, 1),
               "swiglu": Activation(_swiglu, _swiglu_pullback, 2)}


def _pair_rows(tokens, rows):
    """How a buffer's rows move (``ops.expert_rows.PairRows``): Pallas
    kernels where the shapes tile, XLA's gather and scatter-add
    elsewhere."""
    # Pallas is imported where a kernel is called: every tower of the
    # zoo is imported together, and the others' start-up does not pay
    # the two seconds it takes
    from persia_tpu.ops.expert_rows import PairRows

    return PairRows.of(*tokens.shape, rows)


def _through_experts(moves, tokens, w1, w2, token_of, sizes, activation):
    """One buffer of sorted pairs through their experts: its tokens'
    rows gathered, before and after the activation, and what the second
    product gives. Rows past the held groups stay in the
    buffer (its size is static) but no tile of theirs is multiplied, and
    they come out zero."""
    from persia_tpu.ops.grouped_matmul import grouped_matmul

    rows = moves.take(tokens, token_of, sizes)
    pre = grouped_matmul(rows, w1, sizes)
    mid = ACTIVATIONS[activation].forward(pre)
    return rows, pre, mid, grouped_matmul(mid, w2, sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def dispatch_pairs(tokens, w1, w2, weight, order, sizes, rows, per_token,
                   activation):
    """The held pairs of a batch through their experts, weighted and
    summed onto their tokens, ``rows`` pairs at a time: (tokens, hidden)
    float32. ``order`` lists the pairs (token x ``per_token`` + choice)
    sorted by group, held groups first, padded to whole buffers;
    ``sizes`` (held + 1,) counts them. The loop runs as many times as the
    held pairs fill a buffer, a number read on the device, so its
    derivative is written out below. ``activation`` names an entry of
    ``ACTIVATIONS``."""
    count, at = _buffers(order, sizes, rows)
    moves = _pair_rows(tokens, rows)
    token_of, weight = _sorted_pairs(order, weight, per_token)

    def one(i, acc):
        lo, part_sizes = at(i)
        part_of = lax.dynamic_slice(token_of, (lo,), (rows,))
        *_, out = _through_experts(moves, tokens, w1, w2, part_of,
                                   part_sizes, activation)
        return moves.add(acc, out, lax.dynamic_slice(weight, (lo,), (rows,)),
                         part_of, part_sizes)

    return lax.fori_loop(0, count, one, jnp.zeros(tokens.shape, F32))


def _dispatch_fwd(tokens, w1, w2, weight, order, sizes, rows, per_token,
                  activation):
    return (dispatch_pairs(tokens, w1, w2, weight, order, sizes, rows,
                           per_token, activation),
            (tokens, w1, w2, weight, order, sizes))


def _dispatch_bwd(rows, per_token, activation, saved, ct):
    """The same loop: each buffer recomputed, the cotangent of its
    tokens' sums gathered and carried back through the weights, the
    second product, the activation and the first, by the grouped
    product's own two backward calls; sums in float32. A pair's weight
    meets one buffer only: its gradient is written at the pair's sorted
    position and put back in the pairs' order once, after the loop."""
    from persia_tpu.ops.grouped_matmul import grouped_matmul_pullback

    tokens, w1, w2, weight, order, sizes = saved
    count, at = _buffers(order, sizes, rows)
    moves = _pair_rows(tokens, rows)
    token_of, pair_weight = _sorted_pairs(order, weight, per_token)

    def one(i, sums):
        to_tokens, to_w1, to_w2, by_position = sums
        lo, part_sizes = at(i)
        part_of = lax.dynamic_slice(token_of, (lo,), (rows,))
        w = lax.dynamic_slice(pair_weight, (lo,), (rows,))
        gathered, pre, mid, out = _through_experts(
            moves, tokens, w1, w2, part_of, part_sizes, activation)
        g = moves.take(ct, part_of, part_sizes)
        g_out = (g * w[:, None]).astype(out.dtype)
        g_mid, g_w2 = grouped_matmul_pullback(mid, w2, part_sizes, g_out)
        g_rows, g_w1 = grouped_matmul_pullback(
            gathered, w1, part_sizes,
            ACTIVATIONS[activation].pullback(pre, g_mid))
        return (moves.add(to_tokens, g_rows, jnp.ones_like(w), part_of,
                          part_sizes),
                to_w1 + g_w1.astype(F32), to_w2 + g_w2.astype(F32),
                lax.dynamic_update_slice(
                    by_position, jnp.sum(g * out.astype(F32), axis=-1),
                    (lo,)))

    to_tokens, to_w1, to_w2, by_position = lax.fori_loop(
        0, count, one, tuple(jnp.zeros(x.shape, F32)
                             for x in (tokens, w1, w2, order)))
    pairs = weight.shape[0]
    to_weight = jnp.zeros(weight.shape, F32).at[order[:pairs]].set(
        by_position[:pairs], unique_indices=True)
    return (to_tokens.astype(tokens.dtype),
            to_w1.astype(w1.dtype), to_w2.astype(w2.dtype),
            to_weight.astype(weight.dtype), None, None)


dispatch_pairs.defvjp(_dispatch_fwd, _dispatch_bwd)


# a router's scores over all the routed experts, float32
SCORINGS = {"sigmoid": jax.nn.sigmoid,
            "softmax": functools.partial(jax.nn.softmax, axis=-1)}


class SparseExperts(nn.Module):
    """The held experts' part of a routed expert layer, plus the shared
    expert (module docstring: the contract). ``scoring`` names the
    router's rule, an entry of ``SCORINGS``; ``shared_width`` 0 is a
    model without a shared expert: no ``shared_w1``, ``shared_w2`` and
    no ``experts_shared`` scope."""

    experts_routed: int = 128
    experts_held: Sequence[int] = tuple(range(8))
    per_token: int = 6
    expert_width: int = 1856
    shared_width: int = 3712
    scaling: float = 2.5
    out_scale: float = 1.0
    compute_dtype: Any = jnp.bfloat16
    activation: str = "relu2"       # an entry of ACTIVATIONS
    scoring: str = "sigmoid"        # an entry of SCORINGS

    @nn.compact
    def __call__(self, u):
        bs, t, hidden = u.shape
        cd, held = self.compute_dtype, len(self.experts_held)
        act, _, wider = ACTIVATIONS[self.activation]
        w_r = self.param("router", _kernel_init(),
                         (hidden, self.experts_routed), F32)
        w1 = self.param("w1", _kernel_init(),
                        (held, hidden, wider * self.expert_width), F32)
        w2 = self.param("w2", _kernel_init(self.out_scale),
                        (held, self.expert_width, hidden), F32)
        if self.shared_width:
            s1 = self.param("shared_w1", _kernel_init(),
                            (hidden, wider * self.shared_width), F32)
            s2 = self.param("shared_w2", _kernel_init(self.out_scale),
                            (self.shared_width, hidden), F32)
        if self.is_initializing():
            return jnp.zeros_like(u)
        tokens = u.reshape(bs * t, hidden)
        n = tokens.shape[0]

        with jax.named_scope("experts_route"):
            scores = SCORINGS[self.scoring](jnp.dot(
                tokens.astype(F32), w_r, precision=lax.Precision.HIGHEST))
            chosen, weight = route(scores, self.per_token, self.scaling)
            # where each chosen expert sits among the held ones; `held`
            # itself for an expert that lives elsewhere
            local = np.full((self.experts_routed,), held, np.int32)
            local[list(self.experts_held)] = np.arange(held)
            local = jnp.asarray(local)[chosen].reshape(n * self.per_token)
            order = jnp.argsort(local, stable=True)
            sizes = jnp.bincount(local, length=held + 1).astype(jnp.int32)
            self.sow("intermediates", "routed_rows", sizes[:held])

        with jax.named_scope("experts_grouped"):
            cap = pair_buffer_rows(n, self.per_token, held,
                                   self.experts_routed)
            pad = -order.shape[0] % cap     # to whole buffers: trailing rows
            routed = dispatch_pairs(
                tokens.astype(cd), w1.astype(cd), w2.astype(cd),
                weight.reshape(-1), jnp.pad(order, (0, pad)), sizes, cap,
                self.per_token, self.activation)

        if not self.shared_width:
            return routed.astype(cd).reshape(bs, t, hidden)
        with jax.named_scope("experts_shared"):
            shared = _dense(act(_dense(tokens, s1, cd)), s2, cd)
        return (routed + shared.astype(F32)).astype(cd).reshape(
            bs, t, hidden)


class GroupedQueryAttention(nn.Module):
    """Causal softmax attention, ``kv_heads`` key-value heads serving
    ``heads`` query heads. The keys and values are repeated to the query
    heads before the flash kernel and autodiff sums their gradients over
    each group: the kernel streams a key block per (head, query block)
    either way, and its block specs stay as every other caller has
    them.

    ``rope_theta`` None is attention without positions (``*``); a number
    rotates the whole of every query and key head by :func:`rotary`,
    under ``rope_scaling`` (None or a :class:`YarnRule`, which also sets
    the scores' factor, :func:`softmax_scale`). ``window`` None attends
    every earlier key; a number ``w`` the ``w`` latest, itself included
    (query ``t`` sees key ``s`` where ``t - w < s <= t``), through the
    kernels' band schedule, whose calls are named ``window_attention``
    in a trace where the causal ones are ``flash_attention``."""

    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    out_scale: float = 1.0
    compute_dtype: Any = jnp.bfloat16
    window: Any = None
    rope_theta: Any = None
    rope_scaling: Any = None

    @nn.compact
    def __call__(self, u):
        bs, t, hidden = u.shape
        cd, hd = self.compute_dtype, self.head_dim
        wq = self.param("q_proj", _kernel_init(), (hidden, self.heads * hd),
                        F32)
        wk = self.param("k_proj", _kernel_init(),
                        (hidden, self.kv_heads * hd), F32)
        wv = self.param("v_proj", _kernel_init(),
                        (hidden, self.kv_heads * hd), F32)
        wo = self.param("o_proj", _kernel_init(self.out_scale),
                        (self.heads * hd, hidden), F32)
        if self.is_initializing():
            return jnp.zeros_like(u)
        from persia_tpu.ops.flash_attention import flash_attention_masked

        def heads(y, n, turn=False):    # (bs, t, n * hd) -> (bs, n, t, hd)
            y = y.reshape(bs, t, n, hd)
            if turn and self.rope_theta is not None:
                with jax.named_scope("rotary"):
                    y = rotary(y, self.rope_theta,
                               self.rope_scaling).astype(cd)
            return y.transpose(0, 2, 1, 3)

        q = heads(_dense(u, wq, cd), self.heads, turn=True)
        group = self.heads // self.kv_heads
        k = jnp.repeat(heads(_dense(u, wk, cd), self.kv_heads, turn=True),
                       group, axis=1)
        v = jnp.repeat(heads(_dense(u, wv, cd), self.kv_heads), group, axis=1)
        scale = (None if self.rope_scaling is None
                 else softmax_scale(hd, self.rope_scaling))
        # the calls' name in a trace, one for each kind
        with jax.named_scope("flash_attention" if self.window is None
                             else "window_attention"):
            out = flash_attention_masked(q, k, v, causal=True, scale=scale,
                                         window=self.window)
        out = out.transpose(0, 2, 1, 3).reshape(bs, t, self.heads * hd)
        return _dense(out, wo, cd)


class SelectedAttention(nn.Module):
    """Causal grouped-query attention over the ``topk`` keys a learned
    indexer selects for each query (a sparse-attention indexer of the
    DeepSeek-V3.2-Exp kind beside a Qwen3-MoE attention block): returns
    ``(output, index_loss)``.

    Attention: ``q``, ``k``, ``v`` projections without bias, an RMSNorm
    a head on ``q`` and ``k`` (``q_norm``, ``k_norm`` over ``head_dim``),
    :func:`rotary` over the whole of every query and key head, softmax
    over the selected keys only (``ops.flash_attention.
    flash_attention_selected``), an output projection.

    The indexer reads ``stop_gradient`` of the layer's input: ``q_i =
    u index_q`` (``index_heads`` heads of ``index_dim``), one key ``k_i
    = LayerNorm(u index_k)`` for all of them (``index_k_scale``,
    ``index_k_bias``), ``w = u index_w`` times ``index_heads^(-1/2)
    index_dim^(-1/2)``, the first ``index_rope_dim`` features of every
    ``q_i`` head and of ``k_i`` rotated, and ``I[t, s] = sum_j w[t, j]
    relu(q_i[t, j] . k_i[s])`` (``ops.sparse_select``, in tiles of
    ``index_tile`` queries). Query ``t`` attends the ``min(t + 1,
    topk)`` keys ``s <= t`` of largest ``I``; no gradient passes through
    the choice. The indexer learns from ``index_loss`` alone, the
    divergence of its softmax over the selected keys from the
    attention's own head-averaged probabilities there (a constant), a
    mean over the queries; everything else learns from the output
    alone."""

    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    index_heads: int = 16
    index_dim: int = 64
    index_rope_dim: int = 32
    topk: int = 2048
    index_tile: int = 512
    rope_theta: float = 1e7
    eps: float = 1e-6
    out_scale: float = 1.0
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        bs, t, hidden = u.shape
        cd, hd = self.compute_dtype, self.head_dim
        ih, idim = self.index_heads, self.index_dim
        wq = self.param("q_proj", _kernel_init(), (hidden, self.heads * hd),
                        F32)
        wk = self.param("k_proj", _kernel_init(),
                        (hidden, self.kv_heads * hd), F32)
        wv = self.param("v_proj", _kernel_init(),
                        (hidden, self.kv_heads * hd), F32)
        q_norm = self.param("q_norm", nn.initializers.ones, (hd,), F32)
        k_norm = self.param("k_norm", nn.initializers.ones, (hd,), F32)
        wo = self.param("o_proj", _kernel_init(self.out_scale),
                        (self.heads * hd, hidden), F32)
        index_q = self.param("index_q", _kernel_init(), (hidden, ih * idim),
                             F32)
        index_k = self.param("index_k", _kernel_init(), (hidden, idim), F32)
        k_scale = self.param("index_k_scale", nn.initializers.ones, (idim,),
                             F32)
        k_bias = self.param("index_k_bias", nn.initializers.zeros, (idim,),
                            F32)
        index_w = self.param("index_w", _kernel_init(), (hidden, ih), F32)
        if self.is_initializing():
            return jnp.zeros_like(u), jnp.zeros((), F32)
        from persia_tpu.ops import sparse_select
        from persia_tpu.ops.flash_attention import flash_attention_selected

        def turned(x, width):   # the first `width` features of each head
            return jnp.concatenate(
                [rotary(x[..., :width], self.rope_theta),
                 x[..., width:].astype(F32)], axis=-1)

        with jax.named_scope("select_project"):
            q = _rms(_dense(u, wq, cd).reshape(bs, t, self.heads, hd),
                     self.eps) * q_norm
            k = _rms(_dense(u, wk, cd).reshape(bs, t, self.kv_heads, hd),
                     self.eps) * k_norm
            v = _dense(u, wv, cd).reshape(bs, t, self.kv_heads, hd)
            still = lax.stop_gradient(u)
            q_i = _dense(still, index_q, cd, out=F32).reshape(
                bs, t, ih, idim)
            k_i = _dense(still, index_k, cd, out=F32)
            k_i = k_i - jnp.mean(k_i, axis=-1, keepdims=True)
            k_i = _rms(k_i, self.eps) * k_scale + k_bias
            w = _dense(still, index_w, cd, out=F32) * (ih * idim) ** -0.5
        with jax.named_scope("rotary"):
            q, k = (turned(x, hd).astype(cd).transpose(0, 2, 1, 3)
                    for x in (q, k))
            q_i = turned(q_i, self.index_rope_dim).astype(cd)
            k_i = turned(k_i[:, :, None, :],
                         self.index_rope_dim)[:, :, 0].astype(cd)
        tile = sparse_select.tile_of(t, self.index_tile)
        select = sparse_select.select_keys(
            *map(lax.stop_gradient, (q_i, k_i, w)), self.topk, tile)
        self.sow("selections", "selected", select)
        group = self.heads // self.kv_heads
        with jax.named_scope("flash_attention"):    # the calls' name in a trace
            out, lse = flash_attention_selected(
                q, jnp.repeat(k, group, axis=1),
                jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1), select)
        loss = sparse_select.alignment_loss(
            q_i, k_i, w, *map(lax.stop_gradient, (q, k, lse)), select,
            hd ** -0.5, tile)
        with jax.named_scope("select_out"):
            out = out.transpose(0, 2, 1, 3).reshape(bs, t, self.heads * hd)
            return _dense(out, wo, cd), loss


class YarnRule(NamedTuple):
    """A YaRN rotary scaling rule, as a published ``rope_scaling`` record
    of type ``yarn`` gives it (read as DeepSeek-V3's code reads those
    keys): positions trained up to ``original_positions`` stretched by
    ``factor``. Rotary pairs that turn more than ``beta_fast`` times over
    the original positions keep their frequency, those that turn less
    than ``beta_slow`` times have it divided by ``factor``, and a linear
    ramp over the pair index joins the two. ``mscale`` and
    ``mscale_all_dim`` feed :func:`yarn_mscale`: their ratio multiplies
    cos and sin, and the second one's square the softmax scale."""

    factor: float
    original_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor, m):
    """``0.1 m ln(factor) + 1``; 1 where nothing is stretched."""
    return 0.1 * m * np.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(dim, theta, rule):
    """The ``dim // 2`` rotary frequencies under ``rule``, float64:
    ``theta_i ((1 - ramp_i) + ramp_i / factor)`` with ``theta_i = theta
    ** (-2 i / dim)`` and ``ramp_i = clip((i - low) / (high - low), 0,
    1)``, ``low`` and ``high`` the pair indices that turn ``beta_fast``
    and ``beta_slow`` times over the original positions (floor and
    ceiling, clamped to the pairs there are)."""
    half = dim // 2
    plain = theta ** (-np.arange(half, dtype=np.float64) / half)

    def pair_turning(times):
        return (dim * np.log(rule.original_positions / (times * 2 * np.pi))
                / (2 * np.log(theta)))

    low = np.clip(np.floor(pair_turning(rule.beta_fast)), 0, half - 1)
    high = np.clip(np.ceil(pair_turning(rule.beta_slow)), 0, half - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return plain * ((1 - ramp) + ramp / rule.factor)


def softmax_scale(key_width, rule=None):
    """What multiplies the scores of heads ``key_width`` wide: ``1 /
    sqrt(key_width)``, times ``yarn_mscale(factor, mscale_all_dim) ** 2``
    under a rule that sets ``mscale_all_dim``."""
    scale = 1.0 / float(key_width) ** 0.5
    if rule is not None and rule.mscale_all_dim:
        scale *= yarn_mscale(rule.factor, rule.mscale_all_dim) ** 2
    return scale


def rotary(x, theta, rule=None):
    """Rotary position embedding of ``x`` (batch, T, heads, dim), in the
    rotate-half convention: feature ``i`` of the first half pairs with
    feature ``i`` of the second, and the pair at position ``t`` turns by
    the angle ``t * theta ** (-2 i / dim)``, or under a scaling ``rule``
    (:class:`YarnRule`) by ``t`` times its :func:`yarn_frequencies`, cos
    and sin then multiplied by ``yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim)``. Positions are 0..T-1 a
    history. Float32 in and out."""
    t, half = x.shape[1], x.shape[-1] // 2
    if rule is None:
        freq = jnp.exp(-np.log(theta) / half * jnp.arange(half, dtype=F32))
    else:
        freq = jnp.asarray(yarn_frequencies(x.shape[-1], theta, rule), F32)
    angle = jnp.arange(t, dtype=F32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if rule is not None:
        amplitude = (yarn_mscale(rule.factor, rule.mscale)
                     / yarn_mscale(rule.factor, rule.mscale_all_dim))
        if amplitude != 1.0:
            cos, sin = cos * amplitude, sin * amplitude
    x = x.astype(F32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


class LatentAttention(nn.Module):
    """Causal multi-head latent attention with decoupled rotary keys, in
    the uncompressed form training uses: queries through a low-rank pair
    of projections with a norm between them; keys and values built a head
    from one normed latent of ``kv_rank`` features; ``rope_dim`` further
    features of every query head, and one key of that width shared by all
    the heads, carry position through :func:`rotary`. A head's query and
    key are ``[nope | rope]``, ``nope_dim + rope_dim`` wide; its value
    and output are ``v_dim`` wide, which may be another width (the flash
    kernel takes the two apart, neither padded to the other).
    ``rope_scaling`` is None or a :class:`YarnRule`, which changes the
    rotary frequencies and, through :func:`softmax_scale`, the factor the
    kernel multiplies the scores by. ``q_rank`` None is a model without
    the low-rank query: one direct projection ``q_proj`` to every head's
    ``[nope | rope]``. ``positions`` False is a model whose latent
    attention carries no position (other layers do): the ``rope_dim``
    features of every query head and the one shared key of that width
    enter the scores as projected, and nothing is rotated."""

    heads: int = 20
    q_rank: Any = 768               # None: no low-rank query
    kv_rank: int = 512
    nope_dim: int = 192
    rope_dim: int = 64
    v_dim: int = 256
    rope_theta: float = 1e6
    eps: float = 1e-5
    out_scale: float = 1.0
    compute_dtype: Any = jnp.bfloat16
    rope_scaling: Any = None
    positions: bool = True

    @nn.compact
    def __call__(self, u):
        bs, t, hidden = u.shape
        cd, heads = self.compute_dtype, self.heads
        nope, rope, vd = self.nope_dim, self.rope_dim, self.v_dim
        if self.q_rank is None:
            q_proj = self.param("q_proj", _kernel_init(),
                                (hidden, heads * (nope + rope)), F32)
        else:
            q_a = self.param("q_a", _kernel_init(), (hidden, self.q_rank),
                             F32)
            q_norm = self.param("q_norm", nn.initializers.ones,
                                (self.q_rank,), F32)
            q_b = self.param("q_b", _kernel_init(),
                             (self.q_rank, heads * (nope + rope)), F32)
        kv_a = self.param("kv_a", _kernel_init(),
                          (hidden, self.kv_rank + rope), F32)
        kv_norm = self.param("kv_norm", nn.initializers.ones,
                             (self.kv_rank,), F32)
        kv_b = self.param("kv_b", _kernel_init(),
                          (self.kv_rank, heads * (nope + vd)), F32)
        wo = self.param("o_proj", _kernel_init(self.out_scale),
                        (heads * vd, hidden), F32)
        if self.is_initializing():
            return jnp.zeros_like(u)
        from persia_tpu.ops.flash_attention import flash_attention_masked

        rule = self.rope_scaling
        with jax.named_scope("latent_project"):
            if self.q_rank is None:
                q = _dense(u, q_proj, cd)
            else:
                c_q = (_rms(_dense(u, q_a, cd), self.eps)
                       * q_norm).astype(cd)
                q = _dense(c_q, q_b, cd)
            q = q.reshape(bs, t, heads, nope + rope)
            c_kv, k_rope = jnp.split(_dense(u, kv_a, cd), [self.kv_rank],
                                     axis=-1)
            c_kv = (_rms(c_kv, self.eps) * kv_norm).astype(cd)
            kv = _dense(c_kv, kv_b, cd).reshape(bs, t, heads, nope + vd)

            def keys(k_rope):   # a head's [nope | the one shared key]
                return jnp.concatenate(
                    [kv[..., :nope],
                     jnp.broadcast_to(k_rope, (bs, t, heads, rope))],
                    axis=-1)

            if not self.positions:      # as projected
                k = keys(k_rope[:, :, None, :])
        if self.positions:
            with jax.named_scope("rotary"):
                q_rope = rotary(q[..., nope:], self.rope_theta,
                                rule).astype(cd)
                k_rope = rotary(k_rope[:, :, None, :], self.rope_theta,
                                rule).astype(cd)
                q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
                k = keys(k_rope)
        q, k, v = (y.transpose(0, 2, 1, 3) for y in (q, k, kv[..., nope:]))
        with jax.named_scope("flash_attention"):    # the calls' name in a trace
            out = flash_attention_masked(
                q, k, v, causal=True, scale=softmax_scale(nope + rope, rule))
        out = out.transpose(0, 2, 1, 3).reshape(bs, t, heads * vd)
        return _dense(out, wo, cd)


class GatedFeedForward(nn.Module):
    """``down(silu(gate(u)) * up(u))``, gate and up held as one matrix
    ``[gate | up]`` as an expert's are."""

    width: int = 10240
    out_scale: float = 1.0
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        hidden, cd = u.shape[-1], self.compute_dtype
        w1 = self.param("gate_up", _kernel_init(), (hidden, 2 * self.width),
                        F32)
        w2 = self.param("down", _kernel_init(self.out_scale),
                        (self.width, hidden), F32)
        if self.is_initializing():
            return jnp.zeros_like(u)
        return _dense(_swiglu(_dense(u, w1, cd)), w2, cd)


class _Layer(nn.Module):
    """``h + mixer(RMSNorm(h))`` under the mixer's scope name; a mixer
    that returns ``(output, loss)`` (``S``) gives ``(h + output,
    loss)``."""

    mixer: nn.Module
    scope_name: str
    eps: float
    compute_dtype: Any

    @nn.compact
    def __call__(self, h):
        with jax.named_scope(self.scope_name):
            u = RMSNorm(self.eps, self.compute_dtype, name="norm")(h)
            y = self.mixer(u)
            if isinstance(y, tuple):
                return h + y[0], y[1]
            return h + y


def sinkhorn(logits, iters, eps):
    """``exp(logits)`` (.., n, n) made doubly stochastic by ``iters``
    rounds of: every row over its sum + ``eps``, then every column over
    its sum + ``eps``. A ``lax.scan`` over the rounds (unrolled they were
    a third of the compiled step's code), differentiated through all of
    them."""
    def one_round(m, _):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        return m, None

    m, _ = lax.scan(one_round, jnp.exp(logits), None, length=iters)
    return m


def _near_identity_init(streams):
    """A diagonal of 4, row-major: the stream map starts near the
    identity."""
    def init(key, shape, dtype=F32):
        return 4.0 * jnp.eye(streams, dtype=dtype).reshape(shape)
    return init


class _HyperLayer(nn.Module):
    """A sublayer over ``streams`` residual streams (manifold-constrained
    hyper-connections): ``x`` (batch, T, streams * hidden) in the compute
    dtype, stream ``j`` the features ``[j hidden, (j + 1) hidden)`` of a
    position, so that the position's flattened streams ``z`` are its row
    as it lies (the shape ``ops/hyper_connection``'s kernels read). From
    ``z``, in float32, ``m = (z phi) rsqrt(mean(z^2) + hyper_eps)``
    gives three maps, each with its part of ``phi``, a bias ``b`` and a
    scale ``a``: ``pre = sigmoid(a m + b)`` (n,) reads the mixer's input
    out of the streams, ``u = sum_j pre[j] x[j]``; ``post = 2 sigmoid(a
    m + b)`` (n,) writes its output back to each; and ``res =
    sinkhorn(clip(a m + b, *clamp))`` (n, n row-major), doubly
    stochastic, mixes the streams: ``x'[i] = sum_j res[i, j] x[j] +
    post[i] mixer(RMSNorm(u))``. Every pass over the state is a kernel
    of ``ops/hyper_connection`` (``read_out``: ``m``, ``pre`` and ``u``
    from one read, ``u`` in float32 for the mixer's norm, which works in
    float32; ``mix``: ``x'``; their backward passes), ``post`` and
    ``res`` plain ``jnp`` on the few numbers a position has of them.

    A map's three parameters are leaves of their own
    (``hyper_<map>_phi``, ``_bias``, ``_scale``): over the identical
    streams that the expansion hands the first sublayer, ``pre`` only
    scales a normed input and ``res`` mixes equals, and the last
    sublayer's ``res`` is undone by the contraction (its columns sum to
    one), so their gradients there are zero but for rounding, and an
    optimizer's or a comparison's per-leaf statistics should not mix
    them with live ones."""

    mixer: nn.Module
    scope_name: str
    eps: float
    compute_dtype: Any
    streams: int
    sinkhorn_iters: int
    hyper_eps: float
    clamp: Sequence[float]

    @nn.compact
    def __call__(self, x):
        n, hidden = self.streams, x.shape[-1] // self.streams

        def leaves(name, width, bias_init=nn.initializers.zeros):
            return (self.param(f"hyper_{name}_phi", _kernel_init(),
                               (n * hidden, width), F32),
                    self.param(f"hyper_{name}_bias", bias_init, (width,),
                               F32),
                    self.param(f"hyper_{name}_scale",
                               nn.initializers.constant(0.01), (1,), F32))

        phi, bias, scale = zip(leaves("pre", n), leaves("post", n),
                               leaves("res", n * n, _near_identity_init(n)))
        norm = RMSNorm(self.eps, self.compute_dtype, name="norm")
        if self.is_initializing():      # declares, runs no map or mixer
            self.mixer(norm(x[..., :hidden]))
            return x
        from persia_tpu.ops import hyper_connection

        with jax.named_scope("hyper_connection"), \
                jax.named_scope("hyper_maps"):
            u, m, carry = hyper_connection.read_out(
                x, jnp.concatenate(phi, axis=-1), scale[0], bias[0],
                streams=n, eps=self.hyper_eps)
            post, res = (a * part + b for a, b, part in zip(
                scale[1:], bias[1:], jnp.split(m, [n, 2 * n], axis=-1)[1:]))
            post = 2 * jax.nn.sigmoid(post)
            res = sinkhorn(
                jnp.clip(res, *self.clamp).reshape(*m.shape[:-1], n, n),
                self.sinkhorn_iters, self.hyper_eps)
        with jax.named_scope(self.scope_name):
            y = self.mixer(norm(u))
        with jax.named_scope("hyper_connection"), \
                jax.named_scope("hyper_mix"):
            return hyper_connection.mix(carry, y, res, post)


class NextPrediction(nn.Module):
    """A multi-token-prediction module: the item after next from the
    tower's last hidden state (before the final norm) and the rows of
    the next item, ``h'_t = W [RMSNorm(e_{t+1}) | RMSNorm(h_t)]``,
    through ``layer`` (one more block, its own weights), a norm and the
    **tower's** item head, which it is handed. ``rows`` are the item
    slot's (batch, T, hidden), rolled left by one position here; the
    length stays T, and what the last position reads (the first item,
    rolled round) reaches no earlier one under causal mixing: the loss
    leaves it out."""

    layer: Sequence[nn.Module]
    eps: float
    compute_dtype: Any

    @nn.compact
    def __call__(self, h, rows, head):
        cd, hidden = self.compute_dtype, h.shape[-1]
        with jax.named_scope("mtp_merge"):
            e = RMSNorm(self.eps, cd, name="embed_norm")(
                jnp.roll(rows, -1, axis=1))
            u = RMSNorm(self.eps, cd, name="hidden_norm")(h)
            w = self.param("merge", _kernel_init(), (2 * hidden, hidden),
                           F32)
            h = _dense(jnp.concatenate([e, u], axis=-1), w, cd)
        for one in self.layer:
            h = one(h)
        with jax.named_scope("mtp_head"):
            h = RMSNorm(self.eps, cd, name="head_norm")(h)
            return _dense(h, head, cd, out=F32)


class HybridSequenceTower(nn.Module):
    """``pattern`` and every size are constructor data; the defaults are
    one chip's share of a published 52-layer model (its first nine
    layers, 8 of its 128 routed experts, an eighth of its vocabulary).
    Called as every tower is, with the item slot's ``(sequence, mask)``
    as the one embedding input; returns float32 logits (batch, T,
    vocab) over the item table's rows, and with ``mtp_depth`` 1 the
    pair of them with the prediction module's logits for the item after
    next (``parallel.train.next_items_cross_entropy`` is that pair's
    loss)."""

    pattern: str = "MEMEM*EME"
    hidden: int = 2688
    vocab: int = 16384
    eps: float = 1e-5
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    experts_routed: int = 128
    experts_held: Sequence[int] = tuple(range(8))
    experts_per_token: int = 6
    expert_width: int = 1856
    shared_width: int = 3712
    routed_scaling: float = 2.5
    attn_heads: int = 32
    attn_kv_heads: int = 2
    attn_head_dim: int = 128
    compute_dtype: Any = jnp.bfloat16
    expert_activation: str = "relu2"
    dense_width: int = 10240
    latent_heads: int = 20
    latent_q_rank: Any = 768        # None: one direct query projection
    latent_kv_rank: int = 512
    latent_nope_dim: int = 192
    latent_rope_dim: int = 64
    latent_v_dim: int = 256
    rope_theta: float = 1e6
    mtp_depth: int = 0
    rope_scaling: Any = None        # None or a YarnRule
    residual_streams: int = 1
    sinkhorn_iters: int = 20
    hyper_eps: float = 1e-6
    hyper_clamp: Sequence[float] = (-30.0, 30.0)
    latent_positions: bool = True   # False: nothing rotates in `L`
    kda_heads: int = 32
    kda_head_dim: int = 128
    kda_chunk: int = 64
    expert_scoring: str = "sigmoid"     # an entry of SCORINGS
    index_heads: int = 16           # `S`: the indexer beside attn_*
    index_dim: int = 64
    index_rope_dim: int = 32
    select_topk: int = 2048
    index_tile: int = 512
    attn_window: Any = None         # `W`: the keys a query sees

    SCOPES = {"M": "ssm_mixer", "E": "experts", "*": "attention",
              "L": "latent_attention", "D": "dense_ffn",
              "K": "kda_attention", "S": "selected_attention",
              "W": "sliding_attention", "A": "full_attention"}

    def _mixer(self, kind, out_scale):
        """Unbound, so that the layer it is handed to adopts it."""
        cd = self.compute_dtype
        if kind == "M":
            return SSMMixer(self.ssm_heads, self.ssm_head_dim,
                            self.ssm_groups, self.ssm_state,
                            self.conv_kernel, self.chunk, self.eps,
                            out_scale=out_scale, compute_dtype=cd,
                            parent=None)
        if kind == "E":
            return SparseExperts(self.experts_routed,
                                 tuple(self.experts_held),
                                 self.experts_per_token, self.expert_width,
                                 self.shared_width, self.routed_scaling,
                                 out_scale, cd,
                                 activation=self.expert_activation,
                                 scoring=self.expert_scoring, parent=None)
        if kind == "*":
            return GroupedQueryAttention(self.attn_heads, self.attn_kv_heads,
                                         self.attn_head_dim, out_scale, cd,
                                         parent=None)
        if kind in ("W", "A"):
            if kind == "W" and self.attn_window is None:
                raise ValueError(f"pattern {self.pattern!r}: a `W` layer "
                                 f"needs attn_window")
            return GroupedQueryAttention(
                self.attn_heads, self.attn_kv_heads, self.attn_head_dim,
                out_scale, cd,
                window=self.attn_window if kind == "W" else None,
                rope_theta=self.rope_theta,
                rope_scaling=self.rope_scaling if kind == "A" else None,
                parent=None)
        if kind == "L":
            return LatentAttention(self.latent_heads, self.latent_q_rank,
                                   self.latent_kv_rank, self.latent_nope_dim,
                                   self.latent_rope_dim, self.latent_v_dim,
                                   self.rope_theta, self.eps, out_scale, cd,
                                   self.rope_scaling, self.latent_positions,
                                   parent=None)
        if kind == "K":
            return DeltaAttention(self.kda_heads, self.kda_head_dim,
                                  self.conv_kernel, self.kda_chunk, self.eps,
                                  out_scale=out_scale, compute_dtype=cd,
                                  parent=None)
        if kind == "D":
            return GatedFeedForward(self.dense_width, out_scale, cd,
                                    parent=None)
        if kind == "S":
            return SelectedAttention(
                self.attn_heads, self.attn_kv_heads, self.attn_head_dim,
                self.index_heads, self.index_dim, self.index_rope_dim,
                self.select_topk, self.index_tile, self.rope_theta,
                self.eps, out_scale, cd, parent=None)
        raise ValueError(f"pattern {self.pattern!r}: unknown layer {kind!r}")

    @nn.compact
    def __call__(self, non_id_tensors, embedding_tensors, train: bool = False):
        (rows, _mask), = embedding_tensors
        h = rows = rows.astype(self.compute_dtype)
        if self.mtp_depth not in (0, 1):
            raise ValueError(f"mtp_depth {self.mtp_depth}: one prediction "
                             f"module or none")
        streams = self.residual_streams
        if streams > 1 and self.mtp_depth:
            raise ValueError("no prediction module over residual streams")
        selected = "S" in self.pattern
        if selected and (streams > 1 or self.mtp_depth):
            raise ValueError("selected attention runs over one residual "
                             "stream and without a prediction module")
        # output projections start smaller the deeper the stack
        # (the published rescale_prenorm_residual)
        out_scale = 1.0 / len(self.pattern)
        layer = _Layer if streams == 1 else _HyperLayer
        if not self.is_initializing():
            from persia_tpu.ops.flash_attention import RESIDUAL_NAMES
            layer = nn.remat(
                layer, policy=jax.checkpoint_policies.save_only_these_names(
                    *RESIDUAL_NAMES, *KDA_RESIDUAL_NAMES,
                    *SELECT_RESIDUAL_NAMES))
        hyper = () if streams == 1 else (
            streams, self.sinkhorn_iters, self.hyper_eps,
            tuple(self.hyper_clamp))

        def layer_of(kind, **how):
            return layer(self._mixer(kind, out_scale), self.SCOPES[kind],
                         self.eps, self.compute_dtype, *hyper, **how)

        if streams > 1:
            with jax.named_scope("hyper_expand"):   # every stream the row
                h = jnp.concatenate([h] * streams, axis=-1)
        index_loss = jnp.zeros((), F32)     # summed over the `S` layers
        for i, kind in enumerate(self.pattern):
            h = layer_of(kind, name=f"layer_{i}")(h)
            if kind == "S":
                h, part = h
                index_loss = index_loss + part
        if streams > 1:
            with jax.named_scope("hyper_contract"):
                h = sum(h[..., j * self.hidden:(j + 1) * self.hidden].astype(
                    F32) for j in range(streams)).astype(self.compute_dtype)
        with jax.named_scope("item_head"):
            u = RMSNorm(self.eps, self.compute_dtype, name="final_norm")(h)
            w = self.param("item_head", _kernel_init(),
                           (self.hidden, self.vocab), F32)
            logits = _dense(u, w, self.compute_dtype, out=F32)
        if selected:
            return logits, index_loss
        if not self.mtp_depth:
            return logits
        with jax.named_scope("mtp"):
            ahead = NextPrediction(
                [layer_of(kind, parent=None) for kind in self.pattern[-2:]],
                self.eps, self.compute_dtype, name="mtp")(h, rows, w)
        return logits, ahead


def sown(model, params, non_id_tensors, id_tensors, *collections):
    """What the tower's layers sowed into each of ``collections`` over
    one batch (one forward pass), each stacked in layer order."""
    _, state = model.apply({"params": params}, non_id_tensors, id_tensors,
                           train=False, mutable=list(collections))

    def layer_of(path):     # the prediction module's layers come last
        keys = [getattr(k, "key", "") for k in path]
        return ("mtp" in keys,
                min(int(k.split("_")[1]) for k in keys
                    if k.startswith("layer_")))

    def stacked(collection):
        found = jax.tree_util.tree_flatten_with_path(state[collection])[0]
        found = sorted(found, key=lambda kv: layer_of(kv[0]))
        return jnp.stack([leaf for _, leaf in found])

    return [stacked(c) for c in collections]


def routed_rows(model, params, non_id_tensors, id_tensors):
    """For one batch, the rows routed to each held expert of each expert
    layer of ``model`` (a ``DeviceModeModel`` over this tower), as a
    (expert layers, held) int32 array in layer order."""
    return sown(model, params, non_id_tensors, id_tensors,
                "intermediates")[0]


def selected_keys(model, params, non_id_tensors, id_tensors):
    """For one batch, the keys each query of each ``S`` layer attends, as
    the step itself would select them at these parameters: (``S``
    layers, batch, T, T) int8 in layer order, 1 where query ``t``
    attends key ``s``."""
    return sown(model, params, non_id_tensors, id_tensors, "selections")[0]


def selected_pairs(model, params, non_id_tensors, id_tensors):
    """For one batch, the (query, key) pairs each ``S`` layer selected:
    (``S`` layers,) int32 in layer order."""
    return jnp.sum(selected_keys(model, params, non_id_tensors, id_tensors),
                   axis=(1, 2, 3), dtype=jnp.int32)
