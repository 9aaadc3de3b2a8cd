"""Plain reference of DP training of the xLSTM language model.

The equations are those ``repro/nn/xlstm.py`` states (after Beck et al.,
arXiv:2405.04517), written here from scratch in float32 at ``highest``
matmul precision:

- token embedding; 24 pre-norm residual blocks in periods of one sLSTM and
  seven mLSTM blocks; a final RMSNorm (eps 1e-6, scale only) and a linear
  head; per sample, the mean next-token cross-entropy.
- mLSTM: x_i = W_x x, z = W_z x; c = SiLU(causal depthwise conv_4(x_i) + b);
  q = W_q c, k = W_k c / sqrt(d_h), v = x_i, per head; i = sigmoid(g_i),
  log f = log sigmoid(g_f) with (g_i, g_f) = W_g c + b_g; k <- i k and
  v' = (v, i).  Memory S_t = f_t S_{t-1} + k_t v'_t^T and y'_t = q_t S_t,
  computed here in closed form: y'_t = sum_{s<=t} exp(F_t - F_s) (q_t.k_s)
  v'_s with F the cumulative log f.  y = y'[:d_h] / max(|y'[d_h]|, 1); out =
  x + W_o (RMSNorm(y) * SiLU(z)).
- sLSTM: c = SiLU(conv_4(RMSNorm(x)) + b); per position (z, f, i, o) = W_x c
  + b_x + R h_{t-1}; m_t = max(log sigmoid(f) + m_{t-1}, i); i' = exp(i -
  m_t), f' = exp(log sigmoid(f) + m_{t-1} - m_t); c_t = f' c_{t-1} + i'
  tanh(z); n_t = f' n_{t-1} + i'; h_t = sigmoid(o) c_t / max(n_t, 1e-6),
  from h = c = n = 0, m = -1e30.  x <- x + RMSNorm(h); x <- x + W_o(SiLU(W_g
  RMSNorm(x)) * W_u RMSNorm(x)).

Departures, none of them in the mathematics: the mLSTM memory is computed
in its closed form, not by chunks; every block is rematerialized; the
per-sample gradients are taken one sample at a time.  DP-Adam as the train
CLI's step states it: per-sample gradients, each scaled to norm at most R,
summed, plus ``sigma * R * N(0, I)`` drawn from the step's noise key (the
state's key split in three: next key, noise key, policy key), over the
batch size, then Adam.

``quant`` rounds every matmul operand to a lower type (the control);
nothing of the program is imported.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import data, refops

HIGHEST = lax.Precision.HIGHEST


def sizes(cfg: dict) -> dict:
    d = cfg["d_model"]
    di = cfg["mlstm_expand"] * d
    return {"d": d, "di": di, "h": cfg["n_heads"], "dh": di // cfg["n_heads"],
            "k": cfg["conv_k"], "v": cfg["vocab"], "dff": cfg["slstm_ffn"],
            "periods": cfg["n_layers"] // len(cfg["block_pattern"])}


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Dense weights N(0, 1/fan_in), the embedding N(0, 0.02^2), conv taps
    N(0, 1/4), norm scales 1, biases 0 but the forget gates' 3.0; named and
    stacked (one leading axis of periods) as the program holds them."""
    s = sizes(cfg)
    d, di, h, k, dff, n = s["d"], s["di"], s["h"], s["k"], s["dff"], s["periods"]
    counter = iter(range(1 << 20))

    def normal(shape, scale):
        return scale * jax.random.normal(jax.random.fold_in(key, next(counter)), shape)

    def dense(d_in, d_out):
        return {"w": normal((n, d_in, d_out), 1.0 / math.sqrt(d_in))}

    ones = lambda m: {"g": jnp.ones((n, m))}
    conv = lambda m: {"w": normal((n, k, m), 1.0 / math.sqrt(k)), "b": jnp.zeros((n, m))}
    layers = {}
    for i, kind in enumerate(cfg["block_pattern"]):
        if kind == "mlstm":
            gates = dense(di, 2 * h)
            gates["b"] = jnp.zeros((n, 2 * h)).at[:, h:].set(3.0)
            blk = {"norm": ones(d), "in_x": dense(d, di), "in_z": dense(d, di),
                   "conv": conv(di), "q": dense(di, di), "k": dense(di, di), "gates": gates,
                   "out_norm": ones(di), "out_proj": dense(di, d)}
        else:
            wx = dense(d, 4 * d)
            wx["b"] = jnp.zeros((n, 4 * d)).at[:, d:2 * d].set(3.0)
            blk = {"norm": ones(d), "conv": conv(d), "wx": wx, "wr": dense(d, 4 * d),
                   "out_norm": ones(d), "ffn_norm": ones(d),
                   "ffn": {"wg": dense(d, dff), "wu": dense(d, dff), "wo": dense(dff, d)}}
        layers[str(i)] = {"b": blk}
    return {
        "embed": {"e": normal((s["v"], d), 0.02)},
        "layers": layers,
        "norm_f": {"g": jnp.ones((d,))},
        "lm_head": {"w": normal((d, s["v"]), 1.0 / math.sqrt(d))},
    }


class Ops:
    """Matmuls at ``highest``, with operands rounded to ``quant`` if given."""

    def __init__(self, quant: Optional[jnp.dtype]):
        self.quant = quant

    def q(self, x):
        return x if self.quant is None else x.astype(self.quant).astype(jnp.float32)

    def mm(self, x, w):
        return jnp.matmul(self.q(x), self.q(w), precision=HIGHEST)


def rms_norm(x, g, eps=1e-6):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def causal_conv(x, w, b):
    """y_t = sum_j w_j x_{t-K+1+j} + b, zero before the start; x (T, C)."""
    k = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x], axis=0)
    return sum(w[j] * xp[j:j + x.shape[0]] for j in range(k)) + b


def mlstm_block(ops: Ops, s: dict, p: dict, x):
    t = x.shape[0]
    h, dh = s["h"], s["dh"]
    xn = rms_norm(x, p["norm"]["g"])
    xi, z = ops.mm(xn, p["in_x"]["w"]), ops.mm(xn, p["in_z"]["w"])
    c = jax.nn.silu(causal_conv(xi, p["conv"]["w"], p["conv"]["b"]))
    q = ops.mm(c, p["q"]["w"]).reshape(t, h, dh)
    k = ops.mm(c, p["k"]["w"]).reshape(t, h, dh) / math.sqrt(dh)
    g = ops.mm(c, p["gates"]["w"]) + p["gates"]["b"]
    i_gate, log_f = jax.nn.sigmoid(g[:, :h]), jax.nn.log_sigmoid(g[:, h:])
    k = k * i_gate[..., None]
    v = jnp.concatenate([xi.reshape(t, h, dh), i_gate[..., None]], axis=-1)
    cum = jnp.cumsum(log_f, axis=0)  # (T, H)
    decay = cum[:, None, :] - cum[None, :, :]  # (T, S, H)
    causal = jnp.tril(jnp.ones((t, t), bool))[..., None]
    w = jnp.where(causal, jnp.exp(jnp.where(causal, decay, 0.0)), 0.0)
    scores = jnp.einsum("thd,shd->tsh", q, k, precision=HIGHEST) * w
    y = jnp.einsum("tsh,shv->thv", scores, v, precision=HIGHEST)
    y = (y[..., :dh] / jnp.maximum(jnp.abs(y[..., dh]), 1.0)[..., None]).reshape(t, -1)
    y = rms_norm(y, p["out_norm"]["g"]) * jax.nn.silu(z)
    return x + ops.mm(y, p["out_proj"]["w"])


def slstm_block(ops: Ops, s: dict, p: dict, x):
    d = s["d"]
    xn = rms_norm(x, p["norm"]["g"])
    c = jax.nn.silu(causal_conv(xn, p["conv"]["w"], p["conv"]["b"]))
    pre = ops.mm(c, p["wx"]["w"]) + p["wx"]["b"]
    r = p["wr"]["w"]

    def cell(carry, pre_t):
        h, cc, n, m = carry
        u = pre_t + ops.mm(h, r)
        zi, fo, ii, oo = u[:d], u[d:2 * d], u[2 * d:3 * d], u[3 * d:]
        log_f = jax.nn.log_sigmoid(fo)
        m_new = jnp.maximum(log_f + m, ii)
        i_p, f_p = jnp.exp(ii - m_new), jnp.exp(log_f + m - m_new)
        cc = f_p * cc + i_p * jnp.tanh(zi)
        n = f_p * n + i_p
        h = jax.nn.sigmoid(oo) * cc / jnp.maximum(n, 1e-6)
        return (h, cc, n, m_new), h

    zero = jnp.zeros((d,))
    _, hs = lax.scan(cell, (zero, zero, zero, jnp.full((d,), -1e30)), pre)
    x = x + rms_norm(hs, p["out_norm"]["g"])
    hn = rms_norm(x, p["ffn_norm"]["g"])
    f = p["ffn"]
    return x + ops.mm(jax.nn.silu(ops.mm(hn, f["wg"]["w"])) * ops.mm(hn, f["wu"]["w"]),
                      f["wo"]["w"])


def sample_loss(cfg: dict, ops: Ops, params: dict, tokens, labels):
    """Mean next-token cross-entropy of one sample (tokens, labels: (T,))."""
    s = sizes(cfg)
    x = params["embed"]["e"][tokens]

    def period(x, layer_params):
        for i, kind in enumerate(cfg["block_pattern"]):
            blk = mlstm_block if kind == "mlstm" else slstm_block
            x = jax.checkpoint(functools.partial(blk, ops, s))(layer_params[str(i)]["b"], x)
        return x, None

    x, _ = lax.scan(period, x, params["layers"])

    @jax.checkpoint
    def head(x, norm_g, w):
        return jnp.mean(refops.xent(ops.mm(rms_norm(x, norm_g), w), labels))

    return head(x, params["norm_f"]["g"], params["lm_head"]["w"])


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg: "_Frozen", quant: Optional[str]):
    ops = Ops(None if quant is None else jnp.dtype(quant))
    return jax.jit(jax.value_and_grad(functools.partial(sample_loss, dict(cfg), ops)))


def first_steps(cfg: dict, wl: dict, seed: int, n_steps: int, *, quant=None) -> dict:
    """Train ``n_steps`` steps from the seed, one sample at a time.  Returns
    each step's mean loss and (mean, largest) per-sample gradient norm, the
    per-leaf norms of the first step's clipped-gradient sum and of its
    gradient as the optimizer gets it, and those of the parameters'
    change."""
    b, opt_cfg = wl["batch"], wl["optimizer"]
    if wl["mode"] == "non_private":
        raise ValueError("the xLSTM reference follows the DP step only")
    vg = _grad_fn(_Frozen(cfg), quant)
    with jax.default_matmul_precision("highest"):
        init = jax.jit(functools.partial(init_params, cfg))
        params = init(data.stream_key(seed, "weights"))
        opt = refops.adam_init(params)
        make = jax.jit(functools.partial(data.token_batch, batch=b, seq=wl["seq"],
                                         vocab=cfg["vocab"]))
        accumulate = jax.jit(lambda acc, g, c: jax.tree_util.tree_map(
            lambda a, x: a + c * x, acc, g))
        scale = jax.jit(lambda g, c: jax.tree_util.tree_map(lambda x: c * x, g))
        norm = jax.jit(lambda g: jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g))))
        finish = jax.jit(lambda acc, key: jax.tree_util.tree_map(
            lambda a, z: (a + z) / b, acc,
            data.gaussian_noise_like(acc, key, wl["noise_multiplier"] * wl["clip_norm"])))
        adam = jax.jit(functools.partial(
            refops.adam_step, lr=opt_cfg["lr"], b1=opt_cfg["b1"], b2=opt_cfg["b2"],
            eps=opt_cfg["eps"]), static_argnums=(3,))
        rng = data.stream_key(seed, "noise")
        losses, norm_stats, first_grad, first_sum = [], [], None, None
        for step in range(n_steps):
            batch = make(data.stream_key(seed, "data"), step)
            acc, loss_sum, norms = None, 0.0, []
            for i in range(b):
                loss, g = vg(params, batch["tokens"][i], batch["labels"][i])
                norms.append(norm(g))
                c = refops.clip_factors(norms[-1], wl["clip_norm"])
                acc = scale(g, c) if acc is None else accumulate(acc, g, c)
                del g
                loss_sum = loss_sum + loss
            norms = jnp.stack(norms)
            norm_stats.append(jnp.stack([jnp.mean(norms), jnp.max(norms)]))
            if step == 0:
                first_sum = refops.leaf_norms(acc)
            rng, noise_key, _ = jax.random.split(rng, 3)
            grads = finish(acc, noise_key)
            del acc
            if step == 0:
                first_grad = refops.leaf_norms(grads)
            params, opt = adam(params, opt, grads, step + 1)
            del grads
            losses.append(loss_sum / b)
        p0 = init(data.stream_key(seed, "weights"))
        change = jax.jit(lambda p, q: refops.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, p, q)))(params, p0)
    return {
        "names": refops.leaf_names(params),
        "losses": refops.to_numpy(jnp.stack(losses)),
        "norms": refops.to_numpy(jnp.stack(norm_stats)),
        "grad": refops.to_numpy(first_grad),
        "sum": refops.to_numpy(first_sum),
        "change": refops.to_numpy(change),
    }


class _Frozen(dict):
    """A config dict usable as a cache key."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))
