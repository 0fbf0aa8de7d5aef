"""The plain reference of the hybrid decoder (gated delta-rule layers and full
attention layers, SwiGLU, RMSNorm on sublayer outputs) trained online by SGD
on token rows: forward, loss and gradients in straightforward float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``. Nothing of
the program is imported.

What is plain here and is not in the program: the delta rule is the
RECURRENCE OVER SINGLE POSITIONS (no chunks; segments of 64 positions exist
only so that the backward pass recomputes instead of keeping 8,192 states),
attention is a masked softmax over blocks of queries against all keys, the
logits of a row stand whole, matrix products read float32. Gradients are
taken layer by layer (``jax.vjp`` of one layer at a time, its update applied
at once), so that the model at the published widths fits one chip beside one
layer's gradients.

Equations (per layer ``x <- x + Norm(Mixer(x))``, ``x <- x + Norm(FFN(x))``,
``FFN(x) = W_down(silu(W_gate x) * (W_up x))``, RMSNorm with ``rms_norm_eps``):

- full layer: ``q, k, v = W_q x, W_k x, W_v x``; RMSNorm over the whole of
  ``q`` and of ``k``; causal softmax attention per head at ``1/sqrt(head)``;
  ``W_o``; no rotary embedding;
- linear layer, per head: ``q_t, k_t = l2norm(silu(conv(W_q x)_t)),
  l2norm(silu(conv(W_k x)_t))``, ``v_t = silu(conv(W_v x)_t)``, ``beta_t = 2
  sigmoid(w_b x_t)``, ``alpha_t = exp(-exp(A_log) softplus(w_a x_t +
  dt_bias))``, ``S_t = alpha_t S_{t-1}(I - beta_t k_t k_t^T) + beta_t v_t
  k_t^T`` (``S_0 = 0``), ``o_t = S_t q_t / sqrt(dk)``, output ``W_o(RMSNorm(o_t)
  * silu(W_g x_t))``;
- output norm, head, mean next-token cross-entropy over every position of
  the valid rows (position ``i`` predicts token ``i + 1``, the last the
  row's target).

``build(config, precision, fault, params)`` returns the stream's reference:
``feed_file`` follows a probe file's events (a launch is ``batchSize`` rows,
one SGD step; a forecast is answered from the model as it stands), and keeps
``losses``, ``answers`` (id, arg-max token, top-two margin), ``fitted``,
``holdout``, ``params``. ``precision="bfloat16"`` keeps the parameters and
the recurrent state in bfloat16 (the precision below the configuration's);
``fault`` plants one of ``FAULTS``.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

LINEAR, FULL = "linear_attention", "full_attention"
FAULTS = ("chunk_reset", "no_alpha", "beta_not_doubled", "conv_shift",
          "skip_full", "half_loss")
SEGMENT = 64       # positions between two kept states (recomputation only)
QUERY_BLOCK = 512  # queries a block of the score matrix holds
TRAIN, FORECAST = 0, 1


def init_laws(arch: dict) -> dict:
    """``(shape, law)`` for every leaf, ``law(rng, shape)`` drawing it in
    float32: matrices normal(0, 0.02), norm gains 1, conv taps uniform in
    +-1/sqrt(taps), ``A_log = log uniform(1, 16)``, ``dt_bias`` the inverse
    softplus of a step log-uniform in [0.001, 0.1]."""
    f32 = np.float32
    d, f, v = arch["hidden_size"], arch["intermediate_size"], arch["vocab_size"]
    h, dk, dv = arch["linear_num_value_heads"], arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    taps = arch["linear_conv_kernel_dim"]
    normal = lambda rng, shape: f32(0.02) * rng.standard_normal(shape, dtype=f32)
    ones = lambda rng, shape: np.ones(shape, f32)
    tap = lambda rng, shape: (rng.uniform(-1, 1, shape) / np.sqrt(taps)).astype(f32)
    a_log = lambda rng, shape: np.log(rng.uniform(1, 16, shape)).astype(f32)

    def dt_bias(rng, shape):
        dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), shape))
        return (dt + np.log(-np.expm1(-dt))).astype(f32)

    layers = []
    for kind in arch["layer_types"]:
        layer = {"mixer_norm": ((d,), ones), "ffn_norm": ((d,), ones),
                 "w_gate": ((d, f), normal), "w_up": ((d, f), normal), "w_down": ((f, d), normal)}
        if kind == FULL:
            layer.update(wq=((d, d), normal), wk=((d, d), normal), wv=((d, d), normal), wo=((d, d), normal),
                         q_norm=((d,), ones), k_norm=((d,), ones))
        else:
            layer.update(wq=((d, h * dk), normal), wk=((d, h * dk), normal), wv=((d, h * dv), normal),
                         wg=((d, h * dv), normal), wo=((h * dv, d), normal), wa=((d, h), normal), wb=((d, h), normal),
                         conv_q=((taps, h * dk), tap), conv_k=((taps, h * dk), tap), conv_v=((taps, h * dv), tap),
                         A_log=((h,), a_log), dt_bias=((h,), dt_bias), o_norm=((dv,), ones))
        layers.append(layer)
    return {"embed": ((v, d), normal), "layers": layers, "norm": ((d,), ones), "head": ((d, v), normal)}


def init_params(arch: dict, seed: int, cap: int = 0) -> dict:
    """Initial weights of the reference's own, drawn from ``init_laws``. The
    control's two sides share them; against the program the reference starts
    from the program's, and a draw of at most ``cap`` elements a leaf (flat)
    is what the program's are held against: their laws, not their values."""
    rng = np.random.default_rng(seed)
    draw = lambda leaf: leaf[1](rng, (min(int(np.prod(leaf[0])), cap),) if cap else leaf[0])
    return jax.tree_util.tree_map(draw, init_laws(arch), is_leaf=lambda x: isinstance(x, tuple))


def init_ranges(arch: dict) -> dict:
    """``{leaf name: (lowest, highest)}`` of the laws above that have bounds."""
    tap = 1.0 / np.sqrt(arch["linear_conv_kernel_dim"])
    inverse_softplus = lambda dt: dt + np.log(-np.expm1(-dt))
    slack = 1e-6  # float32 beside float64
    return {"conv_q": (-tap - slack, tap + slack), "conv_k": (-tap - slack, tap + slack),
            "conv_v": (-tap - slack, tap + slack), "A_log": (-slack, np.log(16.0) + slack),
            "dt_bias": (inverse_softplus(0.001) - slack, inverse_softplus(0.1) + slack)}


# --- the model ---------------------------------------------------------------


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def causal_conv(x, taps, shift: int = 0):
    """``y_t = sum_j taps[j] x_{t - (K - 1) + j}``; x: [L, C]. ``shift`` is
    the planted fault (the window moved one tap into the past)."""
    k, l = taps.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1 + shift, x.shape[1]), x.dtype), x])
    return sum(taps[j] * xp[j : j + l] for j in range(k))


def delta_recurrence(q, k, v, beta, alpha, state_dtype, reset_every: int):
    """``q, k: [L, H, dk]``, ``v: [L, H, dv]``, ``beta, alpha: [L, H]`` ->
    ``o: [L, H, dv]``, one position at a time."""
    l, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-l) % SEGMENT
    if pad:  # positions that write nothing and are cut off again
        grow = lambda x, fill: jnp.concatenate(
            [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])
        q, k, v, beta, alpha = grow(q, 0), grow(k, 0), grow(v, 0), grow(beta, 0), grow(alpha, 1)
    at = jnp.arange(l + pad)

    def position(s, c):
        q_t, k_t, v_t, beta_t, alpha_t, t = c
        s = s.astype(jnp.float32)
        if reset_every:
            s = jnp.where(t % reset_every == 0, 0.0, s)
        sk = jnp.einsum("hvk,hk->hv", s, k_t)
        s = alpha_t[:, None, None] * (s - beta_t[:, None, None] * sk[:, :, None] * k_t[:, None, :]) \
            + beta_t[:, None, None] * v_t[:, :, None] * k_t[:, None, :]
        s = s.astype(state_dtype)
        o = jnp.einsum("hvk,hk->hv", s.astype(jnp.float32), q_t) / np.sqrt(dk)
        return s, o

    @jax.checkpoint
    def segment(s, c):
        return jax.lax.scan(position, s, c)

    seg = lambda x: x.reshape((-1, SEGMENT) + x.shape[1:])
    _, o = jax.lax.scan(segment, jnp.zeros((h, dv, dk), state_dtype),
                        tuple(seg(x) for x in (q, k, v, beta, alpha, at)))
    return o.reshape((l + pad, h, dv))[:l]


def causal_attention(q, k, v):
    """``[L, H, dh]`` each -> ``[L, H, dh]``: masked softmax, a block of
    queries against all keys at a time."""
    l, h, dh = q.shape
    block = QUERY_BLOCK if l % QUERY_BLOCK == 0 else l
    keys_at = jnp.arange(l)

    @jax.checkpoint
    def one(args):
        q_b, first = args
        s = jnp.einsum("qhd,khd->hqk", q_b, k) / np.sqrt(dh)
        seen = keys_at[None, :] <= (first + jnp.arange(block))[:, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        s = s - jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(one, (q.reshape(l // block, block, h, dh), jnp.arange(0, l, block)))
    return out.reshape(l, h, dh)


def layer_forward(arch: dict, kind: str, fault: Optional[str], state_dtype, p: dict, x):
    """One layer on one row: ``x [L, hidden]`` -> ``[L, hidden]``."""
    eps = arch["rms_norm_eps"]
    l = x.shape[0]
    if kind == FULL and fault == "skip_full":
        return x
    if kind == FULL:
        h = arch["num_attention_heads"]
        dh = arch["hidden_size"] // h
        q = rms_norm(x @ p["wq"], p["q_norm"], eps).reshape(l, h, dh)
        k = rms_norm(x @ p["wk"], p["k_norm"], eps).reshape(l, h, dh)
        v = (x @ p["wv"]).reshape(l, h, dh)
        mixed = causal_attention(q, k, v).reshape(l, h * dh) @ p["wo"]
    else:
        h, dk, dv = arch["linear_num_value_heads"], arch["linear_key_head_dim"], arch["linear_value_head_dim"]
        shift = 1 if fault == "conv_shift" else 0
        q = l2_norm(silu(causal_conv(x @ p["wq"], p["conv_q"], shift)).reshape(l, h, dk))
        k = l2_norm(silu(causal_conv(x @ p["wk"], p["conv_k"], shift)).reshape(l, h, dk))
        v = silu(causal_conv(x @ p["wv"], p["conv_v"], shift)).reshape(l, h, dv)
        beta = 1.0 / (1.0 + jnp.exp(-(x @ p["wb"])))
        if arch["linear_allow_neg_eigval"] and fault != "beta_not_doubled":
            beta = 2.0 * beta
        alpha = jnp.exp(-jnp.exp(p["A_log"]) * jnp.logaddexp(0.0, x @ p["wa"] + p["dt_bias"]))
        if fault == "no_alpha":
            alpha = jnp.ones_like(alpha)
        o = delta_recurrence(q, k, v, beta, alpha, state_dtype,
                             reset_every=SEGMENT if fault == "chunk_reset" else 0)
        gate = silu(x @ p["wg"]).reshape(l, h, dv)
        mixed = (rms_norm(o, p["o_norm"], eps) * gate).reshape(l, h * dv) @ p["wo"]
    x = x + rms_norm(mixed, p["mixer_norm"], eps)
    ffn = (silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    return x + rms_norm(ffn, p["ffn_norm"], eps)


def head_nll(arch: dict, norm, head, x, targets, weights):
    """Sum of ``weights * -log p(target)`` over a row's positions."""
    logits = rms_norm(x, norm, arch["rms_norm_eps"]) @ head
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    return -jnp.sum(weights * jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0])


class Model:
    """The jitted pieces, one layer at a time. Parameters live on the
    default device in ``param_dtype`` and are read in float32."""

    def __init__(self, arch: dict, precision: str, fault: Optional[str]):
        if precision not in ("float32", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.arch, self.fault = arch, fault
        self.param_dtype = jnp.dtype(precision)
        f32 = lambda tree: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)
        self._fwd, self._bwd = {}, {}
        for kind in set(arch["layer_types"]):
            fwd = functools.partial(layer_forward, arch, kind, fault, self.param_dtype)
            self._fwd[kind] = jax.jit(lambda p, x, fwd=fwd: fwd(f32(p), x))

            def bwd(p, x, g, lr, fwd=fwd):
                _, vjp = jax.vjp(fwd, f32(p), x)
                g_p, g_x = vjp(g)
                return self._sgd(p, g_p, lr), g_x

            self._bwd[kind] = jax.jit(bwd, donate_argnums=0)

        def head(norm, head_w, x, targets, weights, lr):
            loss, (g_n, g_h, g_x) = jax.value_and_grad(
                functools.partial(head_nll, arch), argnums=(0, 1, 2)
            )(norm.astype(jnp.float32), head_w.astype(jnp.float32), x, targets, weights)
            return loss, self._sgd(norm, g_n, lr), self._sgd(head_w, g_h, lr), g_x

        self._head = jax.jit(head)
        self._embed_update = jax.jit(
            lambda e, tokens, g, lr: self._sgd(e, jnp.zeros(e.shape, jnp.float32).at[tokens].add(g), lr),
            donate_argnums=0)
        self._logits = jax.jit(
            lambda norm, head_w, x: rms_norm(x, norm.astype(jnp.float32), arch["rms_norm_eps"])
            @ head_w.astype(jnp.float32))

    def _sgd(self, p, g, lr):
        return jax.tree_util.tree_map(
            lambda a, b: (a.astype(jnp.float32) - lr * b).astype(self.param_dtype), p, g)

    def place(self, params: dict) -> dict:
        return jax.tree_util.tree_map(lambda a: jnp.asarray(a, self.param_dtype), params)

    def hidden(self, params: dict, tokens, keep: Optional[list] = None):
        x = params["embed"][tokens].astype(jnp.float32)
        for kind, p in zip(self.arch["layer_types"], params["layers"]):
            if keep is not None:
                keep.append(x)
            x = self._fwd[kind](p, x)
        return x

    def logits_after(self, params: dict, tokens) -> np.ndarray:
        x = self.hidden(params, tokens)[-1:]
        return np.asarray(self._logits(params["norm"], params["head"], x))[0]

    def sgd_step(self, params: dict, rows: np.ndarray, targets: np.ndarray, lr: float):
        """One SGD step on the mean next-token loss of ``rows [B, L]`` with
        the rows' ``targets [B]``; the rows' gradients are summed by applying
        them one after the other to a copy taken first. Returns the loss."""
        b, l = rows.shape
        n_pos = l // 2 if self.fault == "half_loss" else l
        weights = jnp.asarray((np.arange(l) < n_pos) / float(b * n_pos), jnp.float32)
        if b != 1:
            raise NotImplementedError("the reference steps on one row at a time")
        tokens = jnp.asarray(rows[0], jnp.int32)
        shifted = jnp.asarray(np.concatenate([rows[0, 1:], targets[:1]]), jnp.int32)
        inputs: List = []
        x = self.hidden(params, tokens, keep=inputs)
        loss, params["norm"], params["head"], g = self._head(
            params["norm"], params["head"], x, shifted, weights, lr)
        for i in reversed(range(len(inputs))):
            kind = self.arch["layer_types"][i]
            params["layers"][i], g = self._bwd[kind](params["layers"][i], inputs.pop(), g, lr)
        params["embed"] = self._embed_update(params["embed"], tokens, g, lr)
        return float(loss)


# --- the stream --------------------------------------------------------------


class Reference:
    """Follows the probe files' events as the job does: ``batch`` training
    rows are one launch (one SGD step), a file's end launches what is left,
    a forecast is answered from the model as it stands."""

    def __init__(self, config: dict, precision: str, fault: Optional[str],
                 params: Optional[dict] = None):
        learner = config["create"]["learner"]
        self.arch = dict(learner["dataStructure"])
        self.lr = float(learner["hyperParameters"]["learningRate"])
        self.batch = int(config["job_flags"]["batchSize"])
        if self.batch != 1:
            raise NotImplementedError("the reference follows launches of one row")
        self.model = Model(self.arch, precision, fault)
        if params is None:
            params = init_params(self.arch, int(learner["hyperParameters"].get("seed", 0)))
        self.params = self.model.place(params)
        self.losses: List[float] = []
        self.answers: List[tuple] = []  # (forecast id, arg-max token, top-two margin)
        self.fitted = self.holdout = 0

    def feed_file(self, kind: np.ndarray, index: np.ndarray, train_rows, forecast_rows) -> None:
        with jax.default_matmul_precision("highest"):
            for what, i in zip(kind.tolist(), index.tolist()):
                if what == TRAIN:
                    self.losses.append(self.model.sgd_step(
                        self.params, train_rows.tokens[i : i + 1], train_rows.target[i : i + 1], self.lr))
                    self.fitted += 1
                else:
                    logits = self.model.logits_after(self.params, jnp.asarray(forecast_rows.tokens[i], jnp.int32))
                    top = np.argsort(logits)[-2:]
                    self.answers.append((int(i), float(top[1]), float(logits[top[1]] - logits[top[0]])))

    def host_params(self) -> dict:
        return jax.tree_util.tree_map(lambda a: np.asarray(a.astype(jnp.float32)), self.params)


def build(config: dict, precision: str = "float32", fault: Optional[str] = None,
          params: Optional[dict] = None) -> Reference:
    return Reference(config, precision, fault, params)
